"""WindowAggOperator — keyed windowed aggregation on dense TPU state.

The north-star operator (reference: ``WindowOperator.java:98``,
``processElement:300`` / ``onEventTime:459`` / ``emitWindowContents:574``),
re-designed for the MXU/HBM execution model instead of the per-record JVM
loop:

- Keyed state is a **pane ring buffer** in HBM: per accumulator leaf an array
  ``[K_cap, P, *leaf]`` (K_cap = key capacity, P = ring of panes) plus an
  ``int32[K_cap, P]`` element count.  A pane is the gcd-span shared by all
  windows covering it (``assigners.py``); tumbling windows have one pane per
  window, sliding windows share panes across overlapping windows (the blink
  pane optimization, ``HeapWindowsGrouping.java``, made the *only* path).
- ``process_batch`` = one host key-index probe (vectorized, ``keyindex.py``)
  plus ONE jitted device step: lift values, scatter-combine into
  ``(key_slot, pane_slot)`` cells (``ops/scatter.py``).  This replaces the
  reference's per-record ``windowState.add(value)``
  (``WindowOperator.java:422`` → ``HeapAggregatingState.java:42``).
- Watermark advance fires every window whose end it passed, through one of
  two **emit tiers** (device->host bytes are the scarce resource on
  egress-constrained links; unmeasured on a directly attached chip —
  ROADMAP A1):
  * ``device``: a host emit mirror (pane id -> bool[K], maintained from the
    scatter ids the host already computes) yields the exact emit set without
    any device->host metadata traffic; the device gathers just those key
    rows, combines their panes, and downloads ONLY the result values — the
    batched analog of timer-queue polling + ``emitWindowContents``
    (``InternalTimerServiceImpl.advanceWatermark`` → ``onEventTime:459``).
  * ``host``: a write-through host VALUE mirror of the ACC cells (same
    (slot, pane, value) triples as the device scatter, evaluated with the
    aggregate's numpy twins in higher precision) serves fires with ZERO
    device traffic — and can back snapshots (``snapshot_source="mirror"``).
    The device state stays authoritative for sharding/rescale and remains
    continuously equal to the mirror (``verify_mirror``).  ``auto`` picks
    by capability + backend.
- **Allowed lateness** (``WindowOperator.java:630`` cleanup timers): panes are
  retained until ``last_window_end + lateness`` passes the watermark; late
  records within lateness fold into the retained panes and immediately
  re-fire their windows (EventTimeTrigger late-firing semantics); records
  beyond lateness are dropped and counted (side-output hook).
- Count triggers (``CountTrigger.java`` over ``GlobalWindows``) fire per-key
  when the device count crosses the threshold, then purge those keys' state —
  evaluated once per micro-batch (mini-batch semantics, like the reference's
  SQL ``bundle/`` operators).

Static shapes throughout: batches are padded to pow2 sizes (padding rows use
out-of-range slot ids, dropped by XLA scatter), state grows by doubling
(K_cap) / ring doubling (P) — so XLA recompiles only O(log) times per run.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flink_tpu.core.batch import (LONG_MIN, RecordBatch, StreamElement,
                                  TaggedBatch, Watermark)
from flink_tpu.core.functions import (SCATTER_UFUNCS, AggregateFunction,
                                      RuntimeContext)
from flink_tpu.core import keygroups
from flink_tpu.observability import tracing
from flink_tpu.operators.base import (StreamOperator, current_checkpoint_id,
                                      snapshot_is_incremental)
from flink_tpu.runtime.device_health import DeviceQuarantinedError
from flink_tpu.ops.pane_layout import DROP_ID, KeyGrid, PaneRing
from flink_tpu.ops.scatter import combine_along_axis
from flink_tpu.state.keyindex import KeyIndex, ObjectKeyIndex, make_key_index
from flink_tpu.state.paging import identity_grid
from flink_tpu.windowing.assigners import GlobalWindows, WindowAssigner
from flink_tpu.windowing.triggers import EventTimeTrigger, Trigger


def _quantize_cap(n: int) -> int:
    """Static gather width for ``n`` emitted rows: 1/8-pow2 steps — padding
    waste <=12.5%, because the download is the resource the emit tiers
    economise."""
    from flink_tpu.ops.shapes import quantize_pow2
    return quantize_pow2(n, floor=64, steps=8)


def _fetch_enqueue(arrays, chunk_bytes: int = 0):
    """Start async device->host copies of whole arrays; returns a handle for
    :func:`_fetch_collect`.

    Whole-array transfers, deliberately UNCHUNKED: every device op pays a
    fixed round-trip latency, so slicing an array into row chunks
    multiplies that latency per chunk (size of the effect unmeasured on a
    directly attached chip — ROADMAP A1).  ``chunk_bytes`` is accepted for
    call-site compatibility and ignored."""
    sliced = [[a] for a in arrays]
    for chunks in sliced:
        for c in chunks:
            try:
                c.copy_to_host_async()
            except AttributeError:
                pass
    return sliced


def _fetch_collect(sliced):
    out = []
    for chunks in sliced:
        if len(chunks) == 1:
            out.append(np.asarray(chunks[0]))
        else:
            out.append(np.concatenate([np.asarray(c) for c in chunks]))
    return out


def _handle_ready(sliced) -> bool:
    """True when every array's device->host copy has completed."""
    for chunks in sliced:
        for c in chunks:
            try:
                if not c.is_ready():
                    return False
            except AttributeError:
                return True  # no readiness API: treat as ready (will block)
    return True


from flink_tpu.ops.shapes import next_pow2 as _next_pow2  # noqa: E402

#: flat scatter id for padding rows: INT32_MAX is out of range for any
#: K_cap x P state, so the fold discards it at EVERY capacity — unlike K*P,
#: it stays a dropped id across mid-stage key growth
_PAD_ID = DROP_ID


@partial(jax.jit, static_argnums=(0,))
def _snapshot_read_step(layout, state, pane_slot):
    """A checkpoint's device read of one state array: ONE pane column,
    ``[K, ...]`` (``pane_slot`` is ``int32[1]``).  A program with a name of
    its own in a device trace, one per layout and dtype, shared by every
    operator instance — and by every number of live panes: how many a cut
    meets follows the job's pace, and a read shaped by that count
    compiled in the middle of a run the first time the pace changed."""
    with jax.named_scope("pane_gather"):
        return layout.columns(state, pane_slot)[:, 0]


@partial(jax.jit, static_argnums=(0, 1))
def _grow_keys_step(layout, new_k, state, init):
    return layout.grow_keys(state, new_k, init)


@partial(jax.jit, static_argnums=(0, 1))
def _grow_panes_step(layout, new_p, state, init, src_slots, dst_slots):
    return layout.grow_panes(state, new_p, init, src_slots, dst_slots)


@partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
def _set_columns_step(layout, state, pane_slots, cols):
    """A restore's device write of one state array: the first
    ``cols.shape[0]`` key rows of its pane columns ``pane_slots``."""
    return layout.set_columns(state, pane_slots, cols)


class _HotPipeline:
    """Single background worker running hot-path stages IN ORDER.

    The two-stage software pipeline of ``WindowAggOperator.process_batch``:
    the fused host probe/mirror + device dispatch of batch N runs on this
    worker while the main thread returns to the driver (source decode,
    channel IO, the next batch's serial front) and while the device executes
    batch N-1's async dispatch.  Exactly one worker — stages are strictly
    sequential, so state mutation order (and thus every fire digest,
    snapshot, and counter) is identical to the serial path; only the thread
    that runs them changes.  ``depth`` bounds the QUEUE: ``submit`` blocks
    once ``depth`` stages are queued, so at most ``depth + 1`` batches are
    held (queued plus the one executing) — the memory/backpressure bound.

    Errors: a stage exception parks the worker (later stages are skipped)
    and re-raises at EVERY subsequent ``flush()``/``submit()`` — the error
    is STICKY, never consumed: a metrics/REST poller flushing from a
    foreign thread (``job_status()`` -> ``paging_stats()``) must not steal
    the failure from the task thread, whose own next barrier still has to
    fail the task.  Only ``close()`` clears it.
    """

    __slots__ = ("depth", "_q", "_err", "_t")

    def __init__(self, depth: int = 1):
        import queue
        self.depth = max(1, int(depth))
        self._q = queue.Queue(maxsize=self.depth)
        self._err: Optional[BaseException] = None
        self._t = None

    def _loop(self):
        while True:
            fn = self._q.get()
            try:
                if fn is None:
                    return
                if self._err is None:
                    fn()
            except BaseException as e:  # noqa: BLE001 — re-raised at flush
                self._err = e
            finally:
                self._q.task_done()

    def submit(self, fn) -> None:
        if self._err is not None:
            self.flush()
        if self._t is None:
            import threading
            self._t = threading.Thread(target=self._loop, daemon=True,
                                       name="winagg-pipeline")
            self._t.start()
        self._q.put(fn)  # blocks at depth: bounded pipeline

    def pending(self) -> bool:
        return self._q.unfinished_tasks > 0

    def flush(self) -> None:
        """Barrier: block until every submitted stage completed.  A parked
        stage error re-raises here and STAYS parked (see class docstring)."""
        if self._t is not None:
            self._q.join()
        if self._err is not None:
            raise self._err

    def close(self) -> None:
        self._err = None
        if self._t is not None:
            self._q.put(None)
            self._t.join(timeout=10)
            self._t = None


class _Staging:
    """One reusable padded upload set: the int32 flat-id buffer plus one
    pow2-padded buffer per value leaf.  ``token`` is the device array the
    consuming dispatch produced — the set is free for reuse once that
    execution finished (``is_ready``), which protects against backends that
    zero-copy alias host numpy buffers into dispatched computations."""

    __slots__ = ("flat", "bufs", "treedef", "token")

    def __init__(self, Bp: int, leaves, treedef):
        self.flat = np.empty(Bp, np.int32)
        self.bufs = [np.empty((Bp,) + a.shape[1:], a.dtype) for a in leaves]
        self.treedef = treedef
        self.token = None

    def ready(self) -> bool:
        tok = self.token
        if tok is None:
            return True
        try:
            return bool(tok.is_ready())
        except Exception:  # noqa: BLE001 — deleted (donated) or no API:
            return False   # provably-finished unknown -> never reuse

    def fill_values(self, leaves, B: int):
        """Edge-pad the value leaves into the reused buffers; full-width
        leaves pass through uncopied."""
        out = []
        for buf, a in zip(self.bufs, leaves):
            if a.shape[0] == buf.shape[0]:
                out.append(a)  # already pow2: no copy
                continue
            buf[:B] = a
            buf[B:] = a[-1]
            out.append(buf)
        return jax.tree_util.tree_unflatten(self.treedef, out)


def phase_span_name(phase: str) -> str:
    """The span a ``_phase`` emits (profiler trace and journal alike):
    ``window_agg.<phase>``, but for the dispatch, which has carried its
    name in profiler traces since before the phases had spans (the
    benchmark's gap attribution reads it)."""
    if phase == "device_dispatch":
        return "window_agg.device_step"
    return "window_agg." + phase


class WindowAggOperator(StreamOperator):
    """Keyed window aggregation: ``key_by(key_col).window(assigner).aggregate(agg)``."""

    #: sharded-state capability flags, overridden by the mesh subclass
    #: (``parallel/mesh_runtime.MeshWindowAggOperator``): the base operator
    #: treats ``sharding is not None`` as an opaque placement hint and
    #: disables the host emit tier / paging / degraded-tier migration; the
    #: mesh operator owns a key-group-range state LAYOUT (state/shard_layout)
    #: and runs all three per-shard.
    _SHARDED_HOST_TIER = False
    _SHARDED_PAGING = False
    _SHARDED_DEGRADE = False

    def __init__(
        self,
        assigner: WindowAssigner,
        agg: AggregateFunction,
        key_column: str,
        value_selector: Optional[Callable[[Dict[str, Any]], Any]] = None,
        value_column: Optional[str] = None,
        allowed_lateness_ms: int = 0,
        trigger: Optional[Trigger] = None,
        output_column: str = "result",
        emit_window_bounds: bool = True,
        initial_key_capacity: int = 1 << 10,
        initial_panes: int = 16,
        max_batch: int = 1 << 16,
        name: str = "window-agg",
        sharding=None,
        async_fire: bool = False,
        late_output_tag: Optional[str] = None,
        emit_tier: str = "auto",
        snapshot_source: str = "auto",
        native_emit: bool = True,
        device_sync: str = "auto",
        paging=None,
        pipeline_depth: int = 0,
        native_shards: int = 0,
        queryable: Optional[str] = None,
    ):
        #: host tier: use the C++ WinMirror kernels (fused probe+mirror,
        #: compacting fire) when eligible; False pins the numpy mirror —
        #: used by equivalence tests, and the portable fallback either way
        self.native_emit = native_emit
        #: two-stage software pipeline (0 = serial): the hot stage (fused
        #: probe/mirror + paging + device dispatch) of batch N runs on a
        #: background worker, overlapping the driver's serial front for
        #: batch N+1 and the device's async compute of batch N-1.  Barriers
        #: at every state READ — fires, snapshots, watermark advances that
        #: pass a window end, expiry with lateness, verification — keep
        #: fire digests, snapshots, and counters bit-identical to the
        #: serial path; ``depth`` bounds queued stages (at most depth + 1
        #: batches held, queued plus executing).  Count triggers read
        #: device counts inside process_batch, so they pin serial.
        if int(pipeline_depth) < 0:
            raise ValueError("pipeline_depth must be >= 0")
        self.pipeline_depth = int(pipeline_depth)
        self._pipe: Optional[_HotPipeline] = None
        #: native probe shard count (0 = auto: FLINK_TPU_NATIVE_SHARDS or
        #: one per core up to 4).  >1 hash-partitions the fused C probe's
        #: mirror fold across the native worker pool — disjoint slot
        #: ownership, lock-free, bit-identical at any count.
        self.native_shards = int(native_shards)
        self._nm_shards = 1
        #: reusable padded staging sets keyed by (Bp, value tree spec):
        #: scatter-mode dispatch reuses the flat-id and padded value
        #: buffers across batches instead of reallocating per batch
        self._staging_pool: Dict[tuple, List[_Staging]] = {}
        self._nm = None          # NativeWindowMirror when active
        self._nm_tried = False
        #: sideOutputLateData: beyond-lateness records emit as TaggedBatch
        #: on this tag instead of being dropped; the drop counter does NOT
        #: move for side-output rows (reference semantics)
        self.late_output_tag = late_output_tag
        #: opt-in: window emissions materialize on the NEXT operator call
        #: (downloads overlap subsequent device work).  Terminal-sink
        #: pipelines only — downstream event-time operators would see fired
        #: rows after the firing watermark.
        self.async_fire = async_fire
        self._pending_fires: List[tuple] = []
        self.assigner = assigner
        self.agg = agg
        self.key_column = key_column
        self.value_column = value_column
        if value_selector is not None:
            self._select = value_selector
        elif value_column is not None:
            self._select = lambda cols: cols[value_column]
        else:
            self._select = lambda cols: cols
        self.lateness = int(allowed_lateness_ms)
        if trigger is None:
            # GlobalWindows defaults to NeverTrigger (GlobalWindows.java
            # getDefaultTrigger); time windows default to EventTimeTrigger.
            from flink_tpu.windowing.triggers import NeverTrigger
            trigger = (NeverTrigger() if isinstance(assigner, GlobalWindows)
                       else EventTimeTrigger())
        if trigger.fires_on_count and not isinstance(assigner, GlobalWindows) \
                and assigner.panes_per_window != 1 \
                and trigger.purges_on_fire \
                and not agg.supports_retraction():
            raise NotImplementedError(
                "PURGING count triggers over MULTI-PANE (sliding) assigners "
                "need an INVERTIBLE aggregate (all-'add' ACC leaves: "
                "sum/count/avg): overlapping windows share panes, so the "
                "purge is logical — a per-(key, window) value baseline is "
                "subtracted instead of clearing shared cells.  Min/max "
                "cannot retract; use a plain CountTrigger (fire without "
                "purge) for those.")
        self.trigger = trigger
        self.output_column = output_column
        self.emit_window_bounds = emit_window_bounds
        self.name = name
        self.max_batch = max_batch

        self.spec = agg.acc_spec()
        self.kinds = agg.scatter_kind_leaves()

        # ---- cold-key paging (state/paging.py): the pane ring becomes a
        # CACHE over an unbounded key space — K_cap is pinned to
        # paging.capacity, cold keys' pane cells page out to the native
        # SpillStore and back in on access.  Device-tier only: the host
        # value mirror would hold every key in host RAM anyway (its scale
        # story is the spill *backend*), and paging's point is bounding the
        # DEVICE footprint.  Count triggers are excluded — their per-row
        # fire registers don't survive row reassignment.
        self.paging = paging
        self._pager = None
        if paging is not None:
            if sharding is not None and not self._SHARDED_PAGING:
                raise ValueError("paging requires unsharded state (shard "
                                 "first, page within each shard)")
            if isinstance(assigner, GlobalWindows) \
                    or self.trigger.fires_on_count \
                    or not self.trigger.fires_on_time:
                raise ValueError("paging requires time-triggered time "
                                 "windows (no count triggers/GlobalWindows)")
            if emit_tier == "auto":
                emit_tier = "device"
            if emit_tier != "device":
                raise ValueError("paging pins the device emit tier (the "
                                 "host mirror is unbounded host state)")

        # ---- emit tier (VERDICT r2 #1): which memory serves window fires.
        # "device": gather+download emitted rows (the r1/r2 path) — right
        #   when device->host bandwidth is healthy (PCIe, ICI) or state is
        #   sharded.  "host": a write-through HOST VALUE MIRROR of the ACC
        #   cells — maintained from the very same (slot, pane, value)
        #   triples the host computes to build the device scatter — serves
        #   fires with ZERO device->host traffic.  Decisive on
        #   egress-constrained links; what a 1M-key fire's download costs is
        #   unmeasured on a directly attached chip — ROADMAP A1.  The
        #   device state stays
        #   authoritative for sharding/rescale and remains continuously
        #   equal to the mirror (asserted by tests and checkable via
        #   ``verify_mirror``); "auto" picks host exactly when the agg
        #   declares numpy twins (functions.py ``supports_host_emit``), the
        #   state is unsharded, fires are time-triggered, and the backend is
        #   an accelerator (on CPU there is no transfer cost to dodge).
        #   Sharded state is "device" under "auto" on every backend: a mesh
        #   is asked for to put the state on its chips, and the host tier's
        #   "auto" sync cadence may settle on deferred, which leaves them
        #   untouched; the mesh operator's host tier stays there by name.
        host_capable = (
            agg.supports_host_emit()
            and (sharding is None or self._SHARDED_HOST_TIER)
            and self.trigger.fires_on_time
            and not self.trigger.fires_on_count
            and not isinstance(assigner, GlobalWindows))
        if emit_tier == "auto":
            backend = jax.default_backend()
            emit_tier = "host" if (host_capable and sharding is None
                                   and backend != "cpu") else "device"
        if emit_tier == "host" and not host_capable:
            raise ValueError(
                "emit_tier='host' requires an unsharded, time-triggered "
                "window over an aggregate with numpy twins "
                "(AggregateFunction.supports_host_emit)")
        self.emit_tier = emit_tier
        #: which memory backs snapshots: "device" downloads state (the
        #: authoritative copy), "mirror" serializes the host mirror (equal
        #: by construction; zero download).  "auto" follows the emit tier.
        if snapshot_source == "auto":
            snapshot_source = "mirror" if emit_tier == "host" else "device"
        if snapshot_source == "mirror" and emit_tier != "host":
            raise ValueError("snapshot_source='mirror' requires the host "
                             "emit tier")
        self.snapshot_source = snapshot_source
        # ---- device sync cadence (host tier only): how the device replica
        # tracks the authoritative host mirror.  "scatter": every micro-batch
        # dispatches the jitted scatter-combine — the device is continuously
        # current (right on direct PCIe/ICI links, where dispatch is ~free).
        # "deferred": per-record dispatch is skipped and the replica
        # refreshes from the mirror at sync points (``device_refresh``:
        # restore, verification, idle) — right on TAXED transports (proxy
        # links) where executing a dispatched step costs the host tens
        # of CPU-ms per uploaded MB and that CPU is stolen from the native
        # hot path (utils/transport.py; the ingress twin of the emit-tier
        # download finding), and on slow CPU hosts, where the XLA scatter's
        # ~0.5µs/update replica maintenance dwarfs the native mirror fold.
        # "auto" self-calibrates on EVERY backend: the first host-tier
        # operator measures its own first few real update steps and the
        # verdict is shared process-wide; sub-MB batches never sample and
        # settle on scatter (deterministic for unit-sized traffic).
        # Outside the host tier (device fires, sharded/mesh state) the
        # device IS the authority and always scatters.
        if device_sync not in ("auto", "scatter", "deferred"):
            raise ValueError(f"device_sync must be auto|scatter|deferred, "
                             f"got {device_sync!r}")
        if device_sync == "deferred":
            if emit_tier != "host" or (sharding is not None
                                       and not self._SHARDED_HOST_TIER):
                raise ValueError(
                    "device_sync='deferred' requires the unsharded host emit "
                    "tier (the host mirror must be the authoritative copy)")
            if snapshot_source != "mirror":
                raise ValueError(
                    "device_sync='deferred' requires snapshot_source="
                    "'mirror' (device-sourced snapshots would read a stale "
                    "replica)")
        self.device_sync = device_sync
        #: resolved cadence ("scatter"/"deferred"); None until first batch
        self.device_sync_mode: Optional[str] = None
        #: deferred mode: device replica lags the mirror until device_refresh
        self._device_stale = False
        #: auto-calibration attempts so far; bounded so workloads whose
        #: batches are too small to yield a calibration sample settle on
        #: scatter instead of measuring (and blocking) forever
        self._calib_batches = 0
        #: mirror leaf dtypes: integer leaves widen to int64, floats to
        #: float64 — the host tier is the HIGHER-precision replica
        self._mirror_dtypes = tuple(
            np.int64 if np.issubdtype(np.dtype(d), np.integer) else np.float64
            for d in self.spec.leaf_dtypes)
        #: host value mirror: pane id -> [counts int64 [K], leaf_0 [K,...],
        #: ...] (only when emit_tier == "host")
        self._vmirror: Dict[int, list] = {}
        #: per-phase time/byte accounting (bench transparency, VERDICT r2
        #: weak #1): probe/mirror/device_dispatch/fire/snapshot ns, h2d/d2h
        #: bytes
        self.phase_ns = tracing.TimeAccount()
        self.phase_bytes: Dict[str, int] = {}
        #: what caused the work the phases now time (``window_end`` of a
        #: fire, ``checkpoint`` of a cut): an argument of their spans
        self._span_args: Optional[Dict[str, Any]] = None
        #: per-shard phase accounting: phase name -> int64[n_shards] ns,
        #: filled when the fused probe runs sharded with a timing buffer
        #: (the mesh runtime's per-shard probe breakdown; empty otherwise)
        self.phase_shard_ns: Dict[str, np.ndarray] = {}

        # ring geometry — P must exceed the live pane span (window length in
        # panes + out-of-orderness + lateness retention)
        self._P = _next_pow2(max(initial_panes, 2 * assigner.panes_per_window))
        if paging is not None:
            # paged: K_cap is the FIXED resident capacity — the ring never
            # grows with key cardinality (that is the whole point).  The
            # DevicePager itself is created below, AFTER the shard-count
            # divisibility rounding: pager.K must equal the final ring
            # capacity or row assignment and restore overflow
            self._K = _next_pow2(paging.capacity)
        else:
            self._K = _next_pow2(initial_key_capacity)

        #: jax.sharding.Sharding for state arrays (axis 0, which the layout
        #: makes the key-group axis, SURVEY §7.1: ``_layout``).  The jitted
        #: steps of the base class are placement-agnostic: XLA's SPMD
        #: partitioner splits the scatters per shard (indices replicated,
        #: out-of-range rows dropped locally), so multi-chip is pure data
        #: placement — no kernel changes.
        self.sharding = sharding
        # shard count must divide K for even state splits: round K up to
        # lcm(K, n_shards); doubling growth preserves divisibility after that
        if sharding is not None:
            import math
            nsh = max(len(sharding.mesh.devices.reshape(-1))
                      if hasattr(sharding, "mesh") else 1, 1)
            self._K = self._K * nsh // math.gcd(self._K, nsh)
        if paging is not None:
            from flink_tpu.state.paging import DevicePager
            self._pager = DevicePager(paging, self.spec, self._K)
        self.key_index: Optional[KeyIndex | ObjectKeyIndex] = None
        # K x P cells per array, held as ``self._layout`` says
        self._leaves = None          # tuple of device arrays, one per leaf
        self._counts = None          # int32 element counts
        #: sliding count triggers: window id -> int64[<=K] count already
        #: fired per key slot (the CountTrigger count register, which clears
        #: on FIRE — next fire needs n MORE elements)
        self._count_baselines: Dict[int, np.ndarray] = {}
        #: FIRE_AND_PURGE over sliding windows: per-window VALUE baselines
        #: (one np array per ACC leaf) — the fired-so-far accumulator that
        #: gets subtracted from the live pane sum (logical purge; physical
        #: purge would corrupt pane-sharing neighbours)
        self._value_baselines: Dict[int, List[np.ndarray]] = {}
        #: host emit mirror: pane id -> bool[K] "this (key, pane) cell holds
        #: data".  The host computes every scatter id, so it KNOWS which keys
        #: a window will emit — fires upload the exact emit index and
        #: download only the emitted rows' values: no mask/count/index
        #: download (its cost is unmeasured on a directly attached chip —
        #: ROADMAP A1).
        self._mirror: Dict[int, np.ndarray] = {}
        self.pane_base: Optional[int] = None   # smallest retained pane id
        self.max_pane: Optional[int] = None    # largest pane seen
        self.last_fired_window: Optional[int] = None
        self.watermark: int = LONG_MIN
        self.late_dropped: int = 0   # beyond-lateness drop counter (numRecordsDropped)
        self._proc_time: int = LONG_MIN
        #: device-lane health (runtime/device_health.py): True while this
        #: operator runs on the DEGRADED host/numpy tier after the process
        #: -wide monitor quarantined the device.  Host-tier operators keep
        #: folding into their (authoritative) mirror and just stop
        #: dispatching (deferred-sync semantics); device-tier operators
        #: materialize the pane ring into the host value mirror and serve
        #: fires/snapshots from it until re-promotion at a checkpoint-
        #: aligned safe point.
        self._degraded = False
        self._quarantine_migrations = 0
        self._repromotions = 0
        #: tier-transition fencing: every degrade/abandoned-promotion
        #: bumps the epoch; a re-promotion attempt commits only if the
        #: epoch it started under is still current (under _tier_lock), so
        #: a watchdog-abandoned attempt that later limps to completion on
        #: its sacrificed lane thread can never land stale state
        self._tier_epoch = 0
        import threading as _threading
        self._tier_lock = _threading.Lock()

        #: guarded update dispatches so far (``fused_stats``)
        self._hot_dispatches = 0

        # ---- queryable serving tier (ISSUE-9): when named, every fired
        # window's emissions publish into a barrier-free live-read view
        # (queryable/view.py) — the SAME (keys, values) arrays the fire
        # emitted, so a live read is bit-equal to the operator's fire-time
        # values on every tier and mesh size.  Tagged with the watermark +
        # last-completed-checkpoint id they reflect.  None (the default)
        # costs one attribute check per fire and nothing on the record hot
        # path.
        self.queryable = queryable
        self._qview = None
        self._last_completed_checkpoint: Optional[int] = None
        if queryable is not None:
            from flink_tpu.queryable.view import WindowReadView
            self._qview = WindowReadView(key_column)

        # ---- incremental (delta) checkpoints (ISSUE-16): when the runtime
        # enables it, every state mutation marks its (key, pane) cells /
        # baseline windows dirty, and a non-savepoint snapshot ships only
        # the dirt accumulated since the last CONFIRMED checkpoint as a
        # ``window_delta`` increment (runtime/checkpoint/delta.py) instead
        # of the full dense grid.  Off (the default) costs one attribute
        # check per batch.
        self.incremental_state = False
        #: full re-base when dirty cells exceed this fraction of the grid
        self.incr_rebase_ratio = 0.5
        self._incr_clear()

    #: snapshot entries row-indexed by key slot (rescale redistribution)
    ROW_FIELDS = ("leaves", "counts")

    @staticmethod
    def _pack_baselines(snap: Dict[str, Any],
                        windows: Optional[List[int]] = None):
        """dict(window -> slot-row array) → parallel list row-field (the
        redistribute helpers split/concat list-valued row fields per array),
        aligned on ``windows`` (zeros for windows this snapshot lacks)."""
        snap = dict(snap)
        cb = snap.pop("count_baselines", None) or {}
        if windows is None:
            if not cb:
                return snap, ()
            windows = sorted(cb)
        n = next((len(np.asarray(v)) for v in cb.values()),
                 snap["counts"].shape[0] if "counts" in snap else 0)
        snap["count_baseline_windows"] = list(windows)
        snap["count_baseline_rows"] = [
            np.asarray(cb.get(w, np.zeros(n, np.int64))) for w in windows]
        return snap, ("count_baseline_rows",)

    @staticmethod
    def _unpack_baselines(snap: Dict[str, Any]) -> Dict[str, Any]:
        wins = snap.pop("count_baseline_windows", None)
        rows = snap.pop("count_baseline_rows", None)
        if wins:
            snap["count_baselines"] = dict(zip(wins, rows))
        return snap

    @staticmethod
    def split_snapshot(snap: Dict[str, Any], max_parallelism: int,
                       new_parallelism: int) -> List[Dict[str, Any]]:
        """Rescale a snapshot across key-group ranges
        (``StateAssignmentOperation.reDistributeKeyedStates`` analog)."""
        from flink_tpu.state.redistribute import split_keyed_snapshot
        from flink_tpu.state.shard_layout import densify_keyed_snapshot
        snap = densify_keyed_snapshot(snap)  # mesh per-shard slice format
        snap, extra = WindowAggOperator._pack_baselines(snap)
        parts = split_keyed_snapshot(snap, WindowAggOperator.ROW_FIELDS + extra,
                                     max_parallelism, new_parallelism)
        return [WindowAggOperator._unpack_baselines(p) for p in parts]

    @staticmethod
    def merge_snapshots(snaps: List[Dict[str, Any]]) -> Dict[str, Any]:
        """Merge same-checkpoint snapshots (scale-down).

        Subtasks of one coordinated ALIGNED checkpoint share pane
        progress (every subtask saw the same watermark at the barrier);
        an UNALIGNED checkpoint's subtasks snapshot at different
        watermarks (the barrier overtakes each at its own moment), so
        their pane rings cover different-but-overlapping ranges.  The
        keys are disjoint (key-group partitioned), so heterogeneous
        progress merges safely by EXPANDING every part onto the union
        pane range (zero panes a part never reached / already expired)
        and taking the MINIMUM watermark / last-fired-window: windows a
        faster subtask already fired have their state evicted there (no
        double fire), while a slower subtask's unfired windows stay live
        and fire when the restored job's watermark passes them again."""
        from flink_tpu.state.redistribute import merge_keyed_snapshots
        from flink_tpu.state.shard_layout import densify_keyed_snapshot
        snaps = [densify_keyed_snapshot(s) for s in snaps]
        live = [s for s in snaps if "panes" in s]
        if live and any(not np.array_equal(s["panes"], live[0]["panes"])
                        for s in live[1:]):
            snaps = WindowAggOperator._align_pane_progress(snaps)
            live = [s for s in snaps if "panes" in s]
        all_windows = sorted({w for s in snaps
                              for w in (s.get("count_baselines") or {})})
        extra = ()
        if all_windows:
            packed = []
            for s in snaps:
                p, e = WindowAggOperator._pack_baselines(s, all_windows)
                packed.append(p)
                extra = e or extra
            snaps = packed
        merged = merge_keyed_snapshots(snaps,
                                       WindowAggOperator.ROW_FIELDS + extra)
        merged = WindowAggOperator._unpack_baselines(merged)
        if live:
            # MIN is correct for both cases: aligned parts all agree (min
            # == max), unaligned parts must resume from the slowest
            # subtask's progress or its not-yet-fired windows never fire
            merged["watermark"] = min(s["watermark"] for s in live)
            lf = [s.get("last_fired_window") for s in live]
            merged["last_fired_window"] = (None if any(w is None for w in lf)
                                           else min(lf))
        return merged

    @staticmethod
    def _align_pane_progress(snaps: List[Dict[str, Any]]
                             ) -> List[Dict[str, Any]]:
        """Expand each part's pane-indexed row fields onto the UNION pane
        range (contiguous ``arange(min pane_base, max max_pane + 1)``):
        panes a part already expired or never reached hold zero counts,
        which is exactly their state there.  Keys stay disjoint across
        parts, so the subsequent keyed merge concatenates rows without
        ever adding two parts' values for one (key, pane)."""
        live = [s for s in snaps if "panes" in s]
        base = min(int(s["pane_base"]) for s in live)
        top = max(int(s["max_pane"]) for s in live)
        union = np.arange(base, top + 1, dtype=np.int64)
        # the restored ring maps slot = pane % P: P must cover the union
        # span or distinct panes would collide in one slot
        ring = max(int(s.get("P", 2)) for s in live)
        while ring < len(union):
            ring <<= 1
        out = []
        for s in snaps:
            if "panes" not in s:
                out.append(s)
                continue
            s2 = dict(s)
            off = int(s["pane_base"]) - base
            counts = np.asarray(s["counts"])
            n_p = counts.shape[1]
            wide = np.zeros((counts.shape[0], len(union)), counts.dtype)
            wide[:, off:off + n_p] = counts
            s2["counts"] = wide
            leaves = []
            for leaf in s["leaves"]:
                leaf = np.asarray(leaf)
                w = np.zeros((leaf.shape[0], len(union)) + leaf.shape[2:],
                             leaf.dtype)
                w[:, off:off + n_p] = leaf
                leaves.append(w)
            s2["leaves"] = leaves
            s2["panes"] = union
            s2["pane_base"] = base
            s2["max_pane"] = top
            s2["P"] = ring
            out.append(s2)
        return out

    def reset_state(self) -> None:
        """Drop all keyed state/time progress but KEEP compiled steps (the
        jit caches key on this instance).  Used by benchmarks/tests to re-run
        a warm operator, and by restore paths before loading a snapshot."""
        if self._pipe is not None:
            self._pipe.flush()   # in-flight stages still write this state
        self._staging_pool = {}
        self.key_index = None
        self._leaves = None
        self._counts = None
        self._count_baselines = {}
        self._value_baselines = {}
        self._pending_fires = []
        self._mirror = {}
        self._vmirror = {}
        self._nm = None          # keydict died with key_index
        self._nm_tried = False
        self.pane_base = None
        self.max_pane = None
        self.last_fired_window = None
        self.watermark = LONG_MIN
        self.late_dropped = 0
        self._proc_time = LONG_MIN
        self.phase_ns = tracing.TimeAccount()
        self.phase_bytes = {}
        self.phase_shard_ns = {}
        self._hot_dispatches = 0
        self._device_stale = False  # resolved sync mode survives the reset
        self._degraded = False      # fresh state restores on the device
        with self._tier_lock:
            self._tier_epoch += 1   # fence any in-flight promotion
        self._active_rows = None
        if self._pager is not None:
            self._pager.reset()
        self._incr_clear()      # a fresh state has no confirmed delta base

    # ------------------------------------------------------------------ state
    @property
    def _layout(self):
        """How the state arrays hold their K x P cells
        (``ops/pane_layout.py``): the pane-major ring on one chip; the
        key-major grid where the key axis shards under GSPMD placement
        (the mesh operator holds a ring per device: its override)."""
        cls = PaneRing if self.sharding is None else KeyGrid
        return cls(self._K, self._P)

    def _placed(self, arrays):
        """State arrays committed to the operator's sharding, if any."""
        if self.sharding is None:
            return arrays
        return [jax.device_put(a, self.sharding) for a in arrays]

    def _replace_state(self, step, per_array) -> None:
        """Every state array ``a`` (leaves, then counts) becomes
        ``step(a, x)``, ``x`` its entry of ``per_array``."""
        *leaves, self._counts = self._placed([
            step(a, x) for a, x in zip((*self._leaves, self._counts),
                                       per_array)])
        self._leaves = tuple(leaves)

    def _alloc(self, K: int, P: int):
        layout = dataclasses.replace(self._layout, K=K, P=P)
        leaves = [layout.full(init, shape, dtype)
                  for init, shape, dtype in zip(self.spec.leaf_inits,
                                                self.spec.leaf_shapes,
                                                self.spec.leaf_dtypes)]
        *leaves, counts = self._placed(
            leaves + [layout.full(0, (), jnp.int32)])
        return tuple(leaves), counts

    def _ensure_alloc(self):
        if self._leaves is None:
            self._leaves, self._counts = self._alloc(self._K, self._P)

    # -------------------------------------------------------- emit mirror
    def _mirror_mark(self, pane: int, slots: np.ndarray) -> None:
        arr = self._mirror.get(pane)
        if arr is None or arr.size < self._K:
            grown = np.zeros(self._K, bool)
            if arr is not None:
                grown[: arr.size] = arr
            arr = self._mirror[pane] = grown
        arr[slots] = True

    def _mirror_emit_idx(self, panes: np.ndarray) -> np.ndarray:
        """Exact ascending key-slot ids that hold data in any of ``panes``."""
        n = self.key_index.num_keys if self.key_index is not None else 0
        acc = None
        for p in panes.tolist():
            arr = self._mirror.get(int(p))
            if arr is None:
                continue
            a = arr[:n] if arr.size >= n else np.pad(arr, (0, n - arr.size))
            acc = a.copy() if acc is None else (acc | a)
        if acc is None:
            return np.empty(0, np.int64)
        return np.flatnonzero(acc)

    # ---------------------------------------------------- host value mirror
    def _phase(self, name: str):
        """Accumulating timer and span: ``with self._phase("mirror"): ...``
        adds the region's wall time to ``phase_ns[name]`` and the thread's
        CPU time in it to ``phase_ns[name + "_cpu"]`` (``tests/
        test_bench_gate`` scrapes the vocabulary), and emits it as a
        ``hot_stage`` span; ``_span_args`` name what caused the work
        (``window_end`` for a fire, ``checkpoint`` for a cut)."""
        return tracing.PhaseTimer(self.phase_ns, name, phase_span_name(name),
                                  "hot_stage", self._span_args)

    @contextlib.contextmanager
    def _caused_by(self, **args):
        """Per fire / per cut: the phases inside carry ``args`` (those
        that are not None) on their spans, so one result's spans share an
        identifier."""
        prev = self._span_args
        self._span_args = {k: v for k, v in args.items() if v is not None}
        try:
            yield
        finally:
            self._span_args = prev

    def _try_native_mirror(self) -> None:
        """Bind the C++ WinMirror to the (fresh) key index, if eligible.
        Called once per key-index lifetime; ineligible configs (object keys,
        non-scalar leaves, no compiler) keep the numpy mirror."""
        if self._nm_tried or self.emit_tier != "host" or not self.native_emit:
            return
        self._nm_tried = True
        from flink_tpu.state.native_mirror import (NativeWindowMirror,
                                                   calibrated_shards)
        self._nm = NativeWindowMirror.try_create(
            self.key_index, self.spec, self.kinds, self._mirror_dtypes)
        if self._nm is not None:
            # 0 = auto: MEASURED once per process (steal-heavy vCPUs often
            # lose with extra shards — calibrated_shards A/Bs it)
            self._nm_shards = self.native_shards or calibrated_shards()

    def _probe_shards(self):
        """(shards, shard_div, shard_ns) for the fused native probe:
        shard count, contiguous-range ownership divisor (0 = slot %% S
        classes), and an optional int64 per-shard timing buffer.  The mesh
        subclass aligns these with the device mesh (shard t owns the
        key-group range whose state block lives on device t) and collects
        the per-shard breakdown."""
        return self._nm_shards, 0, None

    def _record_shard_ns(self, phase: str, shard_ns) -> None:
        if shard_ns is None:
            return
        acc = self.phase_shard_ns.get(phase)
        if acc is None or acc.size < shard_ns.size:
            grown = np.zeros(shard_ns.size, np.int64)
            if acc is not None:
                grown[:acc.size] = acc
            acc = self.phase_shard_ns[phase] = grown
        acc[:shard_ns.size] += shard_ns

    # ------------------------------------------ shims the benchmark reads
    # ``benchmarks/harness/runner.py`` (``check_healthy``, ``_lanes``) reads
    # these two by name, and no PR but a ``benchmark`` one may edit it
    # (ROADMAP C7): the lanes they described are gone.
    def device_probe_stats(self) -> Dict[str, Any]:
        return {"enabled": 0}

    def fused_stats(self) -> Dict[str, Any]:
        """``hot_dispatches``: guarded update dispatches so far."""
        return {"enabled": 0, "hot_dispatches": self._hot_dispatches}

    # ------------------------------------------------------------- pipeline
    def _pipe_active(self) -> bool:
        """Pipelining applies to the time-triggered hot path only: count
        triggers read device counts inside ``process_batch`` itself, which
        would force a barrier per batch (i.e. the serial path anyway)."""
        return self.pipeline_depth > 0 and not self.trigger.fires_on_count

    def _pipe_pending(self) -> bool:
        return self._pipe is not None and self._pipe.pending()

    def owned_threads(self) -> list:
        """Threads this operator started itself (the hot-stage pipeline's
        worker): their CPU time belongs to the task that runs the
        operator (``Task.thread_cpu_ns``)."""
        worker = self._pipe._t if self._pipe is not None else None
        return [worker] if worker is not None else []

    def flush_pipeline(self) -> List[StreamElement]:
        """Pipeline barrier: complete every in-flight hot stage.  Called
        internally before any state read (fires, snapshots, verification)
        and by task drivers at idle points so pipelined results never wait
        on the NEXT batch's arrival.  Safe no-op when pipelining is off."""
        if self._pipe is not None:
            self._pipe.flush()
        return []

    def _staging_acquire(self, Bp: int, leaves, treedef) -> _Staging:
        key = (Bp, treedef,
               tuple((a.dtype.str, a.shape[1:]) for a in leaves))
        pool = self._staging_pool.setdefault(key, [])
        for st in pool:
            if st.ready():
                st.token = None
                return st
        st = _Staging(Bp, leaves, treedef)
        if len(pool) < 4:  # bounded: beyond that, dispatch is the backlog
            pool.append(st)
        return st

    def _resolve_device_sync(self) -> str:
        """Resolved sync cadence for this batch: "scatter", "deferred", or
        "calibrating" (= scatter + measure this batch's dispatch cost)."""
        if self.device_sync_mode is not None:
            return self.device_sync_mode
        if (self.device_sync == "scatter" or self.emit_tier != "host"
                or (self.sharding is not None
                    and not self._SHARDED_HOST_TIER)
                or self.snapshot_source != "mirror"):
            self.device_sync_mode = "scatter"
        elif self.device_sync == "deferred":
            self.device_sync_mode = "deferred"
        else:  # auto
            # EVERY backend calibrates, the CPU backend included: there the
            # "transport" is the XLA dispatch compute itself — a CPU scatter
            # costs ~0.5µs/update (measured; independent of state size), so
            # on slow boxes the per-batch replica sync dwarfs the entire
            # native mirror fold.  Small-batch workloads never produce a
            # calibration sample (transport.MIN_SAMPLE_MB) and settle on
            # scatter — deterministic for unit-test-sized traffic.
            from flink_tpu.utils import transport
            taxed = transport.dispatch_taxed()
            if taxed is None:
                if self._calib_batches < 8:
                    self._calib_batches += 1
                    return "calibrating"
                # batches too small to ever yield a calibration sample
                # (transport.MIN_SAMPLE_MB): stop probing — scatter,
                # without the per-batch measurement block
                self.device_sync_mode = "scatter"
            else:
                self.device_sync_mode = ("deferred" if taxed
                                         else "scatter")
        return self.device_sync_mode

    def _mirror_columns(self, panes, rows: int,
                        ncols: Optional[int] = None):
        """Dense device-dtype columns of the host mirror: counts int32
        [rows, ncols] plus one [rows, ncols, *shape] array per leaf, column
        j holding pane ``panes[j]`` (missing panes and pad columns =
        identity).  The single source of the mirror export semantics —
        identity fill, int64->int32 counts, mirror->device dtype casts —
        shared by mirror-sourced snapshots and the deferred-sync refresh."""
        ncols = len(panes) if ncols is None else ncols
        counts = np.zeros((rows, ncols), np.int32)
        leaves = []
        for init, shape, d in zip(self.spec.leaf_inits,
                                  self.spec.leaf_shapes,
                                  self.spec.leaf_dtypes):
            arr = np.empty((rows, ncols) + tuple(shape), d)
            arr[...] = np.asarray(init).astype(d)
            leaves.append(arr)
        for j, p in enumerate(panes):
            if self._nm is not None:
                ex, cnts, lvs = self._nm.export_pane(int(p), rows)
                if not ex:
                    continue
                counts[:, j] = cnts  # int64 -> int32 cast
                for dst, src in zip(leaves, lvs):
                    dst[:, j] = src  # mirror -> device dtype cast
            else:
                e = self._vmirror.get(int(p))
                if e is None:
                    continue
                counts[:, j] = e[0][:rows]
                for k, dst in enumerate(leaves):
                    dst[:, j] = e[k + 1][:rows].astype(
                        self.spec.leaf_dtypes[k], copy=False)
        return counts, leaves

    @partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2, 3))
    def _refresh_step(self, layout, leaves, counts, slots, counts_cols,
                      leaf_cols):
        """Replace the whole ring from live-pane COLUMNS: slots i32[m] are
        the live ring slots (pads = P, dropped), counts_cols [rows, m] with
        rows <= K covering the live keys, each leaf col [rows, m, *shape].
        Every other cell resets to identity — the upload scales with live
        panes x live keys, not ring/key capacity."""
        new_counts = layout.set_columns(jnp.zeros_like(counts), slots,
                                        counts_cols)
        new_leaves = tuple(
            layout.set_columns(
                jnp.broadcast_to(jnp.asarray(init, l.dtype), l.shape),
                slots, col)
            for l, init, col in zip(leaves, self.spec.leaf_inits, leaf_cols))
        if self.sharding is not None:
            # the refresh must hand back PRE-PARTITIONED state (out
            # shardings == the update step's in shardings): without the
            # constraint XLA commits the scatter of replicated host
            # columns onto one device and the next dispatch pays a
            # reshard (the compile-once smoke's failure mode)
            new_counts = jax.lax.with_sharding_constraint(new_counts,
                                                          self.sharding)
            new_leaves = tuple(jax.lax.with_sharding_constraint(
                l, self.sharding) for l in new_leaves)
        return new_leaves, new_counts

    def device_refresh(self) -> None:
        """Rebuild the device replica from the authoritative host mirror
        (deferred sync's sync point — restore, verification, idle, or an
        explicit pre-mesh handoff).  Set semantics over the whole ring:
        slots without a live pane reset to identity, which also folds in
        any expirations skipped while deferred; uploaded bytes scale with
        live panes.  No-op when the replica is already current."""
        self.flush_pipeline()
        if self._degraded:
            return  # no refresh while quarantined; re-promotion rebuilds
        if not self._device_stale:
            return
        self._device_stale = False
        if self.key_index is None or self.pane_base is None:
            return
        self._ensure_alloc()
        n = self.key_index.num_keys
        present = (set(self._nm.live_panes().tolist()) if self._nm is not None
                   else set(self._vmirror))
        hi = self.pane_base if self.max_pane is None else self.max_pane
        live = [int(p) for p in range(self.pane_base, hi + 1)
                if int(p) in present]
        m = _next_pow2(max(len(live), 1), 1)  # pad: bounded compile count
        # rows cover live keys only (pow2-quantized for a bounded compile
        # count), not key capacity: a 1M-capacity operator holding 10k keys
        # refreshes ~80KB columns, not ~8MB
        rows = min(_next_pow2(max(n, 1), 1024), self._K)
        slots = np.full(m, self._P, np.int32)  # P = out of range, dropped
        slots[:len(live)] = [p % self._P for p in live]
        counts_cols, leaf_cols = self._mirror_columns(live, rows, ncols=m)
        self._leaves, self._counts = self._refresh_step(
            self._layout, self._leaves, self._counts, slots, counts_cols,
            tuple(leaf_cols))
        self.phase_bytes["h2d_refresh"] = (
            self.phase_bytes.get("h2d_refresh", 0) + counts_cols.nbytes
            + sum(l.nbytes for l in leaf_cols))

    def _vmirror_pane(self, pane: int) -> list:
        """[counts, *leaves] arrays for a pane, allocated/grown to >=
        max(_K, live keys) — a DEGRADED paged operator holds every key in
        the mirror, not just the K_cap-resident prefix."""
        need = self._K
        if self._degraded and self.key_index is not None:
            need = max(need, _next_pow2(max(self.key_index.num_keys, 1)))
        entry = self._vmirror.get(pane)
        if entry is None or entry[0].size < need:
            fresh = [np.zeros(need, np.int64)]
            for init, shape, mdt in zip(self.spec.leaf_inits,
                                        self.spec.leaf_shapes,
                                        self._mirror_dtypes):
                arr = np.empty((need,) + tuple(shape), mdt)
                arr[...] = np.asarray(init).astype(mdt)
                fresh.append(arr)
            if entry is not None:
                n = entry[0].size
                for f, o in zip(fresh, entry):
                    f[:n] = o
            entry = self._vmirror[pane] = fresh
        return entry

    @staticmethod
    def _host_scatter(kind: str, arr: np.ndarray, slots: np.ndarray,
                      vals: np.ndarray) -> None:
        """In-place segment combine ``arr[slots] op= vals`` (numpy twin of
        ops/scatter.py).  add on scalar leaves: one bincount; min/max and
        non-scalar leaves: sort + ufunc.reduceat (ufunc.at is ~50x slower)."""
        if kind == "add" and vals.ndim == 1:
            arr += np.bincount(slots, weights=vals,
                               minlength=arr.size).astype(arr.dtype,
                                                          copy=False)
            return
        ufunc = SCATTER_UFUNCS[kind]
        order = np.argsort(slots, kind="stable")
        ss = slots[order]
        vv = vals[order]
        starts = np.flatnonzero(np.r_[True, ss[1:] != ss[:-1]])
        red = ufunc.reduceat(vv, starts, axis=0)
        uniq = ss[starts]
        arr[uniq] = ufunc(arr[uniq], red)

    def _vmirror_update(self, slots: np.ndarray, panes: np.ndarray,
                        values) -> None:
        """Fold this batch into the host mirror — same (slot, pane, value)
        triples as the device scatter, evaluated with the agg's numpy twins."""
        lifted = jax.tree_util.tree_leaves(self.agg.host_lift(values))
        lifted = [np.asarray(l) for l in lifted]
        for p in np.unique(panes).tolist():
            m = panes == p
            s = slots[m] if not m.all() else slots
            entry = self._vmirror_pane(int(p))
            entry[0] += np.bincount(s, minlength=entry[0].size)
            for j, (kind, leaf) in enumerate(zip(self.kinds, lifted)):
                self._host_scatter(kind, entry[j + 1], s,
                                   leaf[m] if not m.all() else leaf)

    def _fire_window_host(self, window_id: int,
                          panes: np.ndarray) -> List[StreamElement]:
        """Serve a window fire ENTIRELY from the host mirror: no device op,
        no download — the emit path for egress-constrained links."""
        n = self.key_index.num_keys if self.key_index is not None else 0
        if n == 0:
            return []
        if self._nm is not None:
            # one C sweep: combine panes, compact non-empty rows, resolve keys
            keys, _counts, leaves = self._nm.fire(panes)
            if keys.size == 0:
                return []
            result = self.agg.host_get_result(self.spec.unflatten(leaves))
            return self._rows_for_keys(
                keys, result, self.assigner.window_bounds(window_id))
        entries = [self._vmirror[int(p)] for p in panes.tolist()
                   if int(p) in self._vmirror]
        if not entries:
            return []
        total = entries[0][0][:n].copy()
        for e in entries[1:]:
            total += e[0][:n]
        idx = np.flatnonzero(total > 0)
        if idx.size == 0:
            return []
        acc_leaves = []
        for j, kind in enumerate(self.kinds):
            ufunc = SCATTER_UFUNCS[kind]
            leaf = entries[0][j + 1][idx]
            for e in entries[1:]:
                leaf = ufunc(leaf, e[j + 1][idx])
            acc_leaves.append(leaf)
        result = self.agg.host_get_result(self.spec.unflatten(acc_leaves))
        return self._rows_for(idx, result,
                              self.assigner.window_bounds(window_id))

    def verify_mirror(self, atol: float = 1e-3, rtol: float = 1e-4) -> bool:
        """Consistency check: download the device state for live panes and
        compare against the host mirror (the device is the authoritative
        replica; the mirror must be its higher-precision twin).  Costly on
        slow links — meant for tests and sampled bench validation.

        Under deferred sync the replica is refreshed first, so the check
        validates the refresh round trip (mirror -> upload -> download ->
        compare: ring mapping, dtype casts, expiry folds) rather than
        continuous per-batch equality — which deferred mode by design does
        not maintain between sync points."""
        self.flush_pipeline()
        if self._degraded:
            return True  # replica intentionally stale/absent in quarantine
        if self.device_sync_mode == "deferred":
            self.device_refresh()
        if self.emit_tier != "host" or self._leaves is None \
                or self.pane_base is None:
            return True
        n = self.key_index.num_keys if self.key_index else 0
        layout = self._layout
        for p in range(self.pane_base, (self.max_pane or 0) + 1):
            slot = self._pane_slots([p])
            dev_counts = np.asarray(
                layout.columns(self._counts, slot, rows=n))[:, 0]
            if self._nm is not None:
                _ex, cnts, lvs = self._nm.export_pane(p, n)
                host = [cnts] + lvs
            else:
                host = self._vmirror.get(p)
            host_counts = (host[0][:n] if host is not None
                           else np.zeros(n, np.int64))
            if not np.array_equal(dev_counts, host_counts):
                return False
            for j in range(self.spec.num_leaves):
                dev = np.asarray(
                    layout.columns(self._leaves[j], slot, rows=n),
                    np.float64)[:, 0]
                hst = (np.asarray(host[j + 1][:n], np.float64)
                       if host is not None
                       else np.broadcast_to(np.asarray(
                           self.spec.leaf_inits[j], np.float64), dev.shape))
                # compare in DEVICE precision: the mirror carries more bits
                hst32 = hst.astype(self.spec.leaf_dtypes[j]).astype(np.float64)
                if not np.allclose(dev, hst32, atol=atol, rtol=rtol,
                                   equal_nan=True):
                    return False
        return True

    def _round_key_capacity(self, needed: int) -> int:
        """pow2 growth; subclasses may strengthen (e.g. mesh divisibility).
        Paged state never grows: overflow pages out instead."""
        if self._pager is not None:
            return self._K
        return _next_pow2(needed, self._K)

    def _grow_keys(self, needed: int):
        newK = self._round_key_capacity(needed)
        if newK == self._K and self._leaves is not None:
            return
        old = self._layout
        self._K = newK
        # grow EVERY live mirror pane with the capacity: a pane untouched
        # after the growth must still serve fires/snapshots at the new key
        # count (the lazy per-touch grow only covers touched panes)
        for p in list(self._vmirror):
            self._vmirror_pane(p)
        if self._leaves is None:
            self._leaves, self._counts = self._alloc(self._K, self._P)
            return
        self._replace_state(
            lambda a, init: _grow_keys_step(old, newK, a,
                                            np.asarray(init, a.dtype)),
            (*self.spec.leaf_inits, 0))

    def _grow_panes(self, span: int):
        """Double the pane ring until it holds ``span`` live panes, remapping
        slot = pane % P_old -> pane % P_new for retained panes."""
        newP = self._P
        while newP < span:
            newP <<= 1
        if newP == self._P:
            return
        old = self._layout
        self._P = newP
        if self._leaves is None or self.pane_base is None:
            self._leaves, self._counts = self._alloc(self._K, newP)
            return
        panes = np.arange(self.pane_base, self.max_pane + 1, dtype=np.int64)
        src = (panes % old.P).astype(np.int32)
        dst = (panes % newP).astype(np.int32)
        self._replace_state(
            lambda a, init: _grow_panes_step(
                old, newP, a, np.asarray(init, a.dtype), src, dst),
            (*self.spec.leaf_inits, 0))

    # ------------------------------------------------------------- device ops
    @partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2, 3))
    def _update_step(self, layout, leaves, counts, flat_ids, values):
        """One micro-batch fold: lift + scatter-combine into the state as
        it is held (``layout.fold``).  flat_ids are the host's
        ``row * P + slot``; any id at or past ``K * P`` (the padding rows'
        ``_PAD_ID``) is dropped."""
        # the named scopes are each stage's name in a device trace: an
        # `XLA Ops` event's op_name says which of them asked for it
        with jax.named_scope("lift"):
            lifted = tuple(jax.tree_util.tree_leaves(self.agg.lift(values)))
        new_leaves, new_counts = layout.fold(
            leaves, counts, flat_ids, lifted, self.kinds,
            self.agg.combine_leaves)
        # scalar completion token: ready exactly when THIS execution
        # finished — the staging-reuse gate (new_counts itself is donated
        # into the next step, so its own readiness is unobservable)
        with jax.named_scope("completion_token"):
            return new_leaves, new_counts, new_counts[(0,) * new_counts.ndim]

    def _fire_acc_core(self, layout, leaves, counts, pane_slots,
                       k_active: int):
        """Shared fire body: the window's pane columns of the live rows,
        combined.  k_active (static): only the first k_active key rows are
        live, so fire cost scales with live keys, not allocated capacity
        (0 = every row)."""
        rows = k_active or None
        with jax.named_scope("pane_gather"):
            sel = tuple(layout.columns(l, pane_slots, rows=rows)
                        for l in leaves)
        with jax.named_scope("count_total"):
            total = layout.columns(counts, pane_slots, rows=rows).sum(axis=1)
        with jax.named_scope("pane_combine"):
            combined = combine_along_axis(sel, self.agg.combine_leaves,
                                          axis=1)
        return total > 0, combined

    @partial(jax.jit, static_argnums=(0, 1, 5))
    def _fire_step(self, layout, leaves, counts, pane_slots, k_active: int):
        mask, combined = self._fire_acc_core(layout, leaves, counts,
                                             pane_slots, k_active)
        with jax.named_scope("get_result"):
            return mask, self.agg.get_result(self.spec.unflatten(combined))

    @partial(jax.jit, static_argnums=(0, 1, 5))
    def _fire_acc_step(self, layout, leaves, counts, pane_slots,
                       k_active: int):
        """Like ``_fire_step`` but returns the combined ACCUMULATOR leaves
        (pre-``get_result``): the purging-count-trigger path subtracts the
        per-window value baseline from the acc before producing output."""
        return self._fire_acc_core(layout, leaves, counts, pane_slots,
                                   k_active)

    def _pane_slots(self, panes: np.ndarray):
        """Ring slots of ``panes`` as a device int32 vector.  Cast on the
        host: ``jnp.asarray(int64_array, jnp.int32)`` runs a program of its
        own (``jit(convert_element_type)``) for every fire, clear and cut."""
        return jnp.asarray(
            (np.asarray(panes, np.int64) % self._P).astype(np.int32))

    def _read_columns(self, panes, n: int) -> List[np.ndarray]:
        """The first ``n`` key rows of the pane columns ``panes`` of every
        state array (leaves, then counts) on the host: ``[n, len(panes),
        ...]`` each, a transposed view of the columns as they arrive."""
        layout = self._layout
        with self._phase("snapshot_d2h"):
            # column by column: launch every read, then wait for the
            # device (every step queued ahead of them first) and copy
            slots = [self._pane_slots([p]) for p in np.asarray(panes)]
            handle = _fetch_enqueue([
                _snapshot_read_step(layout, a, slot)
                for a in (*self._leaves, self._counts) for slot in slots])
            cols = _fetch_collect(handle)
        self.phase_bytes["snapshot_column_reads"] = \
            self.phase_bytes.get("snapshot_column_reads", 0) + len(cols)
        with self._phase("snapshot_assemble"):
            m = len(slots)
            return [np.moveaxis(np.stack([c[:n] for c in
                                          cols[i * m:(i + 1) * m]]), 0, 1)
                    for i in range(len(self._leaves) + 1)]

    def _set_columns(self, pane_slots, leaf_cols, counts_cols) -> None:
        """Restore: write host columns ``[n, live, ...]`` over the first
        ``n`` key rows of the pane columns ``pane_slots``."""
        layout = self._layout
        self._replace_state(
            lambda a, cols: _set_columns_step(layout, a, pane_slots,
                                              np.asarray(cols)),
            (*leaf_cols, counts_cols))

    def _k_active(self) -> int:
        """Static pow2 bound on live key rows (0 = use full capacity).
        Sharded state skips slicing: the slice would break even row
        distribution across devices."""
        if self.sharding is not None or self.key_index is None:
            return 0
        # ×4 growth steps: every distinct value is one XLA compile of the fire
        # step — coarse quantization caps the compile count at ~5 per run
        # (paged: live rows are bounded by the assigned-row high-water mark,
        # not by key cardinality)
        n = (self._pager.row_high_water if self._pager is not None
             else self.key_index.num_keys)
        ka = 4096
        while ka < n:
            ka <<= 2
        return min(ka, self._K)

    @partial(jax.jit, static_argnums=(0, 1))
    def _fire_gather_step(self, layout, leaves, pane_slots, idx):
        """Fire for a host-known emit set: the window's panes combined for
        the ``idx`` key rows only (the download scales with rows
        *emitted*, not key capacity), then ``get_result``.  The emit index
        is host-derived from the mirror — nothing but result values ever
        rides the (slow) device->host direction.  The batched analog of
        the reference emitting only non-empty windows
        (``WindowOperator.emitWindowContents:574``)."""
        combined = layout.combine_panes_at(leaves, pane_slots, idx,
                                           self.agg.combine_leaves)
        with jax.named_scope("get_result"):
            return self.agg.get_result(self.spec.unflatten(combined))

    def _fire_window_gather(self, window_id: int,
                            panes: np.ndarray) -> List[StreamElement]:
        """Mirror-indexed fire (unsharded state): exact emit set from the
        host mirror, one values-only download."""
        with self._phase("fire_dispatch"):
            # the emit set from the host mirror, then the gather's launch
            # and its device->host copies started: nothing waits here
            idx = self._mirror_emit_idx(panes)
            n = idx.size
            if n == 0:
                return []
            cap = _quantize_cap(n)
            idx_p = np.zeros(cap, np.int32)
            idx_p[:n] = idx
            pane_slots = self._pane_slots(panes)
            result = self._fire_gather_step(self._layout, self._leaves,
                                            pane_slots, jnp.asarray(idx_p))
            handle = _fetch_enqueue(jax.tree_util.tree_leaves(result))
            treedef = jax.tree_util.tree_structure(result)
        if self._pager is not None:
            # rows -> global ids NOW: by the time an async fire drains, a
            # row may have been evicted and reassigned to another key
            idx = self._pager.gid_of[idx]
        if self.async_fire:
            self._pending_fires.append((window_id, idx, handle, treedef))
            return []
        return self._finish_gather_fire(window_id, idx, handle, treedef)

    def drain_pending_fires(self, force: bool = False) -> List[StreamElement]:
        """Materialize async fire downloads IN ORDER, but only those whose
        transfers completed (unless ``force``): blocking on an in-flight
        download would re-serialize it with the next batch's device work —
        the whole point of async_fire is that fires stream out in the
        background.  Depth is bounded so memory stays bounded."""
        if not self._pending_fires:
            return []
        if len(self._pending_fires) > 3:
            force = True
        out: List[StreamElement] = []
        while self._pending_fires:
            window_id, idx, handle, treedef = self._pending_fires[0]
            if not force and not _handle_ready(handle):
                break
            self._pending_fires.pop(0)
            out.extend(self._finish_gather_fire(window_id, idx, handle,
                                                treedef))
        return out

    def _finish_gather_fire(self, window_id: int, idx: np.ndarray, handle,
                            treedef) -> List[StreamElement]:
        with self._phase("fire_d2h"):
            # blocks until the device has run every step queued ahead of
            # the gather, the gather, and the copy to the host
            fetched = _fetch_collect(handle)
        nbytes = sum(f.nbytes for f in fetched)
        self.phase_bytes["d2h"] = self.phase_bytes.get("d2h", 0) + nbytes
        self.phase_bytes["d2h_fire"] = \
            self.phase_bytes.get("d2h_fire", 0) + nbytes
        with self._phase("fire_assemble"):
            n = idx.size
            picked = jax.tree_util.tree_unflatten(
                treedef, [r[:n] for r in fetched])
            return self._rows_for(idx, picked,
                                  self.assigner.window_bounds(window_id))

    def _rows_for(self, idx: np.ndarray, result,
                  window) -> List[StreamElement]:
        """Shared emit-row assembly (dense/packed/fallback fire paths).
        ``idx`` are global key-index slots; paged fire paths translate
        their HBM rows to global ids AT FIRE TIME (an eviction between an
        async fire and its drain must not re-attribute the emissions)."""
        keys = np.asarray(self.key_index.reverse_keys())[idx]
        return self._rows_for_keys(keys, result, window)

    def _rows_for_keys(self, keys: np.ndarray, result,
                       window) -> List[StreamElement]:
        n = len(keys)
        cols: Dict[str, Any] = {self.key_column: keys}
        if isinstance(result, dict):
            cols.update(result)
        else:
            cols[self.output_column] = result
        if self._qview is not None:
            # queryable live view: retain this fire's (keys, values) arrays
            # — every fire path (host mirror, device gather, spilled
            # chunks, degraded tier, mesh) funnels through here, so live
            # reads are bit-equal to fire-time values by construction
            self._qview.publish(
                keys, {c: v for c, v in cols.items()
                       if c != self.key_column},
                window, self.watermark, self._last_completed_checkpoint)
        if self.emit_window_bounds:
            # constant columns as 0-strided broadcast views: a 1M-row fire
            # would otherwise first-touch ~24MB of np.full pages per window
            cols["window_start"] = np.broadcast_to(
                np.int64(window.start), (n,))
            cols["window_end"] = np.broadcast_to(np.int64(window.end), (n,))
        ts = np.broadcast_to(np.int64(window.max_timestamp), (n,))
        return [RecordBatch(cols, timestamps=ts)]

    @partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2, 3))
    def _clear_panes_step(self, layout, leaves, counts, pane_slots):
        with jax.named_scope("pane_clear"):
            new_leaves = tuple(
                layout.fill_columns(l, pane_slots, init)
                for l, init in zip(leaves, self.spec.leaf_inits))
            return new_leaves, layout.fill_columns(counts, pane_slots, 0)

    @partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2, 3))
    def _purge_keys_step(self, layout, leaves, counts, key_mask):
        """Count-trigger purge: reset fired keys' state (FIRE_AND_PURGE)."""
        new_leaves = tuple(
            layout.where_rows(l, key_mask, init)
            for l, init in zip(leaves, self.spec.leaf_inits))
        return new_leaves, layout.where_rows(counts, key_mask, 0)

    # --------------------------------------------------------------- batching
    #: the span this operator opens around its own ``process_batch``, and
    #: its time (a chain counts it under that name and times nothing)
    span = "window_agg.process_batch"

    def span_time_ns(self):
        return (self.phase_ns.get("process_batch", 0),
                self.phase_ns.get("process_batch_cpu", 0))

    def process_batch(self, batch: RecordBatch) -> List[StreamElement]:
        """The operator's whole entry, phase ``process_batch``: less the
        phases inside it, that is the untimed front (columns, panes, the
        lateness gate, the re-fire check)."""
        with self._phase("process_batch"):
            return self._process_batch(batch)

    def _process_batch(self, batch: RecordBatch) -> List[StreamElement]:
        pending = self.drain_pending_fires() if self.async_fire else []
        if len(batch) == 0:
            return pending
        step = max(self._K // 2, 1) if self._pager is not None else 0
        if step and len(batch) > step:
            # a batch's distinct keys (plus their eviction protections) must
            # fit the resident capacity: split oversized batches (comparing
            # against the clamped step keeps K_cap=1 from recursing forever)
            out = list(pending)
            for lo in range(0, len(batch), step):
                out.extend(self._process_batch(
                    batch.take(np.arange(lo, min(lo + step, len(batch))))))
            return out
        cols = batch.columns
        keys = np.asarray(cols[self.key_column])
        if self.key_index is None:
            self.key_index = make_key_index(keys[0] if keys.ndim else keys,
                                            capacity_hint=self._K)
        if self.assigner.is_event_time:
            if batch.timestamps is None:
                raise ValueError(
                    "event-time window requires timestamps "
                    "(assign_timestamps_and_watermarks upstream)")
            ts = np.asarray(batch.timestamps, np.int64)
        else:
            ts = np.full(len(batch), self._now_ms(), np.int64)
        panes = self.assigner.pane_of(ts)

        # ---- late-beyond-lateness drop, judged EXACTLY like the reference
        # (``WindowOperator.isElementLate``): a record is late iff its pane's
        # last covering window's cleanup time (end - 1 + lateness) has been
        # passed by time — NEVER by arrival order, so a parallel source
        # racing ahead cannot make slower sources' records unstorable
        # the gate's clock follows the assigner's time DOMAIN: wall-clock
        # _proc_time ticks even on event-time operators (periodic timer
        # service) and must never be compared against event-time panes
        gate_now = (self.watermark if self.assigner.is_event_time
                    else self._proc_time)
        if gate_now != LONG_MIN and not isinstance(self.assigner,
                                                   GlobalWindows):
            # candidate panes via [min, max] arange (batch panes are a few
            # contiguous values; np.unique over the batch costs ~ms each).
            # A wide span (straggler records) would turn the per-candidate
            # Python lateness calls below into the cost, so fall back to
            # the distinct panes then.
            p0, p1 = int(panes.min()), int(panes.max())
            cand = (np.arange(p0, p1 + 1, dtype=np.int64)
                    if p1 - p0 < 64 else np.unique(panes))
            is_late = np.asarray(
                [self.assigner.last_window_end_of_pane(int(p)) - 1
                 + self.lateness <= gate_now for p in cand.tolist()])
            if not is_late.any():
                live = np.ones(0, bool)  # nothing late: skip the gate body
            elif np.all(is_late[:-1] >= is_late[1:]):
                # lateness is a prefix of ascending panes (monotone cleanup
                # times): one vector compare instead of isin
                live = panes > int(cand[int(is_late.sum()) - 1])
            else:
                live = ~np.isin(panes, cand[is_late])
            if live.size and not live.all():
                if self.late_output_tag is not None:
                    # sideOutputLateData: rows are shipped, NOT dropped —
                    # the drop counter must stay at the reference semantics
                    # (WindowOperator.java:437 increments only when no side
                    # output consumes the element)
                    pending = list(pending) + [TaggedBatch(
                        self.late_output_tag, batch.select(~live))]
                else:
                    self.late_dropped += int(np.count_nonzero(~live))
                batch = batch.select(live)
                if len(batch) == 0:
                    return pending
                cols = batch.columns
                keys = np.asarray(cols[self.key_column])
                ts = ts[live]
                panes = panes[live]

        pmin, pmax = int(panes.min()), int(panes.max())
        values = self._select(cols)
        if self.incremental_state:
            # delta checkpoints: every (key, pane) this batch touches stays
            # dirty until a checkpoint containing it is CONFIRMED; raw keys
            # resolve to gids lazily at cut time (the index is append-only)
            self._incr_mark_batch(keys, panes)
        if self._pipe_active():
            # two-stage software pipeline: the hot stage (probe/mirror +
            # paging + device dispatch) runs on the background worker while
            # the main thread returns to the driver and the device executes
            # earlier dispatches.  Every state READ barriers through
            # flush_pipeline() (fires, snapshots, expiry, verification), so
            # observable behaviour is bit-identical to the serial path.
            if self._pipe is None:
                self._pipe = _HotPipeline(self.pipeline_depth)
            B = len(batch)
            self._pipe.submit(lambda: self._hot_stage(keys, panes, values,
                                                      B, pmin, pmax))
        else:
            self._hot_stage(keys, panes, values, len(batch), pmin, pmax)

        out: List[StreamElement] = list(pending)
        # ---- count-trigger (GlobalWindows / countWindow path)
        if self.trigger.fires_on_count:
            if isinstance(self.assigner, GlobalWindows):
                out.extend(self._fire_by_count())
            else:
                # CountTrigger over tumbling time windows: fire (key, window)
                # cells whose element count crossed the threshold
                out.extend(self._fire_count_in_panes(np.unique(panes)))
        # ---- late re-fire: windows already passed by the watermark that this
        # batch updated fire again immediately (EventTimeTrigger.onElement FIRE)
        if (self.trigger.fires_on_time and self.assigner.is_event_time
                and self.last_fired_window is not None
                # refire needs a touched pane of an already-fired window:
                # impossible when even the OLDEST touched pane's first window
                # is beyond the fired horizon (the common in-order case) —
                # skips the np.unique below, ~ms per hot-path batch
                and self.assigner.windows_of_pane(pmin)[0]
                <= self.last_fired_window):
            # re-fires read the mirror/device state: barrier first (rare —
            # only batches touching already-fired windows land here)
            self.flush_pipeline()
            touched = np.unique(panes)
            refire: List[int] = []
            for p in touched.tolist():
                w0, w1 = self.assigner.windows_of_pane(int(p))
                for w in range(w0, w1 + 1):
                    max_ts = self.assigner.window_bounds(w).max_timestamp
                    # only windows whose OWN cleanup horizon is still open:
                    # a sliding pane can outlive an early covering window
                    # the reference would already have purged
                    if (w <= self.last_fired_window
                            and max_ts <= self.watermark
                            and max_ts + self.lateness > self.watermark):
                        refire.append(w)
            for w in sorted(set(refire)):
                out.extend(self._fire_window(w))
        return out

    def _hot_stage(self, keys: np.ndarray, panes: np.ndarray, values,
                   B: int, pmin: int, pmax: int) -> None:
        """The hot stage of one micro-batch: pane-ring bookkeeping and
        growth, then either the quarantined device tier's numpy fold or
        the sync cadence's verdict and ONE call to ``_hot_stage_fold``.
        Runs inline when pipelining is off, on the ``_HotPipeline`` worker
        when on — the SAME code in the SAME order either way, so fire
        digests, snapshots, and counters cannot diverge between the two
        modes."""
        if self.pane_base is None:
            self.pane_base = pmin
            self.max_pane = pmax
        else:
            # grow BEFORE extending the live range: the remap copies the
            # old [pane_base, max_pane], which is alias-free only in the
            # old ring geometry.  The range extends DOWNWARD too — a
            # parallel source racing ahead must not make earlier panes
            # unstorable (only truly expired panes drop in the gate).
            new_base = min(self.pane_base, pmin)
            span = max(self.max_pane, pmax) - new_base + 1
            if span > self._P:
                self._grow_panes_guarded(span)
            self.pane_base = new_base
            self.max_pane = max(self.max_pane, pmax)
        span = self.max_pane - self.pane_base + 1
        if span > self._P:
            self._grow_panes_guarded(span)

        if self._degraded and self.emit_tier != "host":
            # quarantined device tier: the host value mirror is the
            # authority — key probe + numpy fold only (no paging, no
            # device dispatch); fires and snapshots read the mirror until
            # re-promotion at a checkpoint-aligned safe point
            with self._phase("probe"):
                slots = self.key_index.lookup_or_insert(keys)
            with self._phase("mirror"):
                # grow EVERY live pane with the key count (the _grow_keys
                # invariant): the per-touch growth below only covers this
                # batch's panes, and an UNTOUCHED pane must still serve
                # fires/snapshots/re-promotion at the new key count —
                # mixed entry sizes would break the pane combine
                for p in list(self._vmirror):
                    self._vmirror_pane(p)
                self._vmirror_update(slots, panes, values)
            return

        self._try_native_mirror()
        sync = self._resolve_device_sync()
        if self._degraded:
            # quarantined HOST tier: the mirror is authoritative anyway —
            # skip the replica dispatch (deferred-sync semantics) until
            # re-promotion
            sync = "deferred"
        self._hot_stage_fold(keys, panes, values, B, sync)

    def _hot_stage_fold(self, keys: np.ndarray, panes: np.ndarray, values,
                        B: int, sync: str) -> None:
        """The fold half of the hot stage for one micro-batch: the key
        probe (fused with the value mirror's write-through on the host
        tier), key growth, paging, and — unless ``sync`` is "deferred" —
        the padded staging and the guarded ``_update_step`` dispatch, then
        the emit mirror's marks."""
        staging = None
        flat_ready = False
        # flatten the value tree ONCE per batch: staging acquisition and
        # the padded fill both consume (leaves, treedef)
        val_leaves = None
        val_treedef = None

        def flat_values():
            nonlocal val_leaves, val_treedef
            if val_leaves is None:
                val_leaves = [np.asarray(a) for a in
                              jax.tree_util.tree_leaves(values)]
                val_treedef = jax.tree_util.tree_structure(values)
            return val_leaves, val_treedef
        if self._nm is not None:
            # fused C pass: key probe + mirror write-through + device scatter
            # ids (the triples are computed once and consumed twice —
            # VERDICT r3 next #1b), sharded across the native worker pool
            # when native_shards > 1.  Deferred sync needs no scatter ids.
            with self._phase("probe_mirror"):
                lifted = [np.asarray(l) for l in jax.tree_util.tree_leaves(
                    self.agg.host_lift(values))]
                nshards, shard_div, shard_ns = self._probe_shards()
                if sync == "deferred":
                    slots = self._nm.probe_update(keys, panes, lifted,
                                                  shards=nshards,
                                                  shard_div=shard_div,
                                                  shard_ns=shard_ns)
                else:
                    # the C pass writes flat ids + padding tail straight
                    # into the reusable staging buffer — dispatch-ready
                    lv, td = flat_values()
                    staging = self._staging_acquire(_next_pow2(B, 64),
                                                    lv, td)
                    slots = self._nm.probe_update(
                        keys, panes, lifted, pane_mod=self._P,
                        flat_out=staging.flat, flat_fill=int(_PAD_ID),
                        shards=nshards, shard_div=shard_div,
                        shard_ns=shard_ns)
                    flat_ready = True
                self._record_shard_ns("probe_mirror", shard_ns)
        else:
            with self._phase("probe"):
                slots = self.key_index.lookup_or_insert(keys)
        if self._pager is None and self.key_index.num_keys > self._K:
            self._ensure_alloc()
            self._grow_keys(self.key_index.num_keys)

        self._ensure_alloc()
        gids = slots   # pre-paging GLOBAL ids: the quarantine-migration
        #                fold must be gid-indexed, not HBM-row-indexed
        if self._pager is not None:
            # translate global key ids -> resident HBM rows, paging cold
            # keys out / promoted keys in (batched device dispatches).
            # Pipelined or not, the pager sees this batch's slots BEFORE
            # any later batch can influence eviction decisions: stages are
            # strictly ordered on the single pipeline worker.
            with self._phase("paging"):
                slots = self._page_slots(slots)
        if sync == "deferred":
            # taxed transport: skip the per-batch dispatch; the mirror (the
            # authoritative copy in this mode) absorbs the batch above and
            # the device replica catches up at the next device_refresh()
            self._device_stale = True
        else:
            # ---- pad to pow2 batch size into REUSED staging buffers
            # (static shapes; pads dropped via the out-of-range _PAD_ID)
            with self._phase("stage"):
                lv, td = flat_values()
                if staging is None:
                    staging = self._staging_acquire(_next_pow2(B, 64),
                                                    lv, td)
                flat_p = staging.flat
                if not flat_ready:
                    flat_p[:B] = slots.astype(np.int64) * self._P \
                        + (panes % self._P)
                    flat_p[B:] = _PAD_ID
                values_p = staging.fill_values(lv, B)

            # np (not device) ids: the jit converts at dispatch, and the mesh
            # subclass re-routes them through the all_to_all exchange
            # host-side
            t_cal = time.perf_counter() if sync == "calibrating" else 0.0
            mb = (flat_p.nbytes + sum(a.nbytes for a in
                                      jax.tree_util.tree_leaves(values_p)))
            try:
                # the phase's span is `window_agg.device_step`: the
                # hand-off to the lane thread (`dispatch_handoff`), the
                # thunk there (`launch`), and the way back
                # (`dispatch_return`)
                with self._phase("device_dispatch"):
                    res = self._guarded_update(flat_p, values_p, mb / 1e6)
            except DeviceQuarantinedError as err:
                # the device tier wedged mid-batch: migrate to the host
                # tier and fold THIS batch there — no record is dropped
                self._enter_degraded(err)
                with self._phase("mirror"):
                    if self.emit_tier == "host":
                        if self._nm is None:  # nm already folded in probe
                            self._vmirror_update(slots, panes, values)
                    else:
                        self._vmirror_update(gids, panes, values)
                return
            if len(res) == 3:
                # the staging set frees once this execution's token is ready
                self._leaves, self._counts, staging.token = res
            else:
                # subclass override without a completion token (mesh): gate
                # reuse on the counts array itself — donated next step, so
                # ready() only passes when the execution provably finished
                self._leaves, self._counts = res
                staging.token = self._counts
            self.phase_bytes["h2d"] = self.phase_bytes.get("h2d", 0) + mb
            if sync == "calibrating":
                # self-calibration: dispatch-call PLUS until-ready wall of
                # this REAL step is the honest replica-sync cost — backends
                # whose dispatch is synchronous (CPU) pay inside the call,
                # async transports pay in the wait; measuring only the wait
                # would read a synchronous backend as free.  Compile/queue
                # noise is filtered by transport.py taking the min across
                # samples.
                from flink_tpu.utils import transport
                jax.block_until_ready(self._counts)
                transport.record_dispatch_cost(mb / 1e6,
                                               time.perf_counter() - t_cal)

        # host emit mirror: record which (key, pane) cells this batch filled
        # (unsharded device tier; the host tier's value mirror carries exact
        # counts, subsuming the boolean mirror; sharded fires read the
        # device mask instead)
        if self.emit_tier == "host":
            if self._nm is None:  # native path already folded in probe_mirror
                with self._phase("mirror"):
                    self._vmirror_update(slots, panes, values)
        elif self.sharding is None or self._pager is not None:
            # paged mesh state keeps the emit mirror too: the gather fire
            # and spilled-key fire both index it (gid-invariant host state)
            uniq_panes = np.unique(panes)
            if uniq_panes.size == 1:
                self._mirror_mark(int(uniq_panes[0]), slots)
            else:
                for p in uniq_panes.tolist():
                    self._mirror_mark(int(p), slots[panes == p])

    # ------------------------------------------- device-lane health (tiers)
    def _grow_panes_guarded(self, span: int) -> None:
        """Ring growth, degraded-aware: a quarantined DEVICE-tier operator
        has no device ring (state lives in the host value mirror, keyed by
        pane ID — no slot remap exists to run), so only ``_P`` advances;
        re-promotion allocates at the final geometry."""
        if self._degraded and self.emit_tier != "host":
            while self._P < span:
                self._P <<= 1
            return
        self._ensure_alloc()
        self._grow_panes(span)

    def _guarded_update(self, flat_p, values_p, mb: float):
        """The jitted update dispatch under the device-health watchdog
        (``runtime/device_health.py``): bounded deadline derived from the
        measured dispatch cost, transient-error retry with backoff, OOM ->
        forced page-out through the DevicePager, wedge -> process-wide
        quarantine (the caller migrates tiers).  Retry assumes the failure
        preceded buffer donation — true for the dispatch-level failures
        the monitor models (the chaos point fires before the thunk; real
        XLA dispatch rejections happen before execution consumes donated
        buffers)."""
        from flink_tpu.runtime import device_health
        # geometry change => this dispatch RECOMPILES (the jit keys on
        # K/P/batch shapes): grant the compile grace so state growth on a
        # slow host never reads as a wedge under a tight deadline floor
        leaves = jax.tree_util.tree_leaves(values_p)
        geom = (self._K, self._P, int(flat_p.shape[0]),
                tuple((a.dtype.str, a.shape[1:]) for a in leaves))
        fresh_geom = geom != getattr(self, "_last_dispatch_geom", None)
        self._last_dispatch_geom = geom
        self._hot_dispatches += 1
        # `hops`: the dispatch's two thread crossings go into `phase_ns`
        # (`dispatch_handoff`, `dispatch_return`) beside the phases the
        # thunk times on the lane thread (`launch`, on a mesh
        # `exchange_route` before it): the parts of `device_dispatch`
        return device_health.guarded_dispatch(
            lambda: self._launch_update(flat_p, values_p),
            mb=mb,
            on_oom=(self._forced_page_out if self._pager is not None
                    else None),
            label=f"{self.name}.update_step",
            compile_grace=fresh_geom, hops=self.phase_ns)

    def _launch_update(self, flat_p, values_p):
        """What a guarded update runs, on the dispatch lane's thread: the
        jitted call alone, phase ``launch`` (it holds the wait for room
        in the device's queue).  The mesh operator routes the batch to
        its shards first."""
        with self._phase("launch"):
            return self._update_step(self._layout, self._leaves,
                                     self._counts, flat_p, values_p)

    def _enter_degraded(self, err: BaseException) -> None:
        """Quarantine migration: leave the device tier MID-JOB.  Host-tier
        operators just stop dispatching (their mirror is already the
        authority); device-tier operators materialize the live pane ring
        through the dense gid-indexed snapshot path into the host value
        mirror (both pager tiers merged), then drop the device arrays.
        Operators with no host twin tier (no numpy twins, sharded state,
        count triggers) re-raise — the task fails and the normal restart
        strategy recovers it from the last checkpoint instead."""
        if (not self.agg.supports_host_emit()
                or (self.sharding is not None and not self._SHARDED_DEGRADE)
                or self.trigger.fires_on_count
                or isinstance(self.assigner, GlobalWindows)):
            raise err
        self._quarantine_migrations += 1
        if self.emit_tier == "host":
            self._degraded = True
            self._device_stale = True
            return
        n = self.key_index.num_keys if self.key_index is not None else 0
        if self._leaves is not None and self.pane_base is not None and n:
            panes = self._live_panes()

            def _salvage_gather():
                if self._pager is not None:
                    return self._paged_snapshot_rows(n, panes)
                *lv, cnt = self._read_columns(panes, n)
                return cnt, lv

            try:
                # the salvage runs under its own bounded deadline on the
                # monitor's lane: a REALLY wedged device hangs the read
                # too, and the migration must never hang the task thread
                from flink_tpu.runtime import device_health
                mon = device_health.get_monitor(create=False)
                if mon is not None:
                    counts, leaves = mon.run_salvage(
                        _salvage_gather, label=f"{self.name} migration")
                else:
                    counts, leaves = _salvage_gather()
            except Exception as gather_err:  # noqa: BLE001
                # a REAL watchdog timeout abandons the dispatch mid-flight
                # with the state buffers already DONATED into it, or the
                # wedged device cannot serve the download within the
                # salvage deadline: the resident state is genuinely
                # unrecoverable in-process — fail the task so the restart
                # strategy recovers from the last checkpoint instead of
                # silently losing panes (or hanging forever)
                raise err from gather_err
            self._degraded = True   # _vmirror_pane sizes past K_cap now
            self._vmirror = {}
            for j, p in enumerate(panes.tolist()):
                if not counts[:, j].any():
                    continue
                entry = self._vmirror_pane(int(p))
                entry[0][:n] = counts[:, j]
                for k, src in enumerate(leaves):
                    entry[k + 1][:n] = src[:, j].astype(
                        self._mirror_dtypes[k])
        self._degraded = True
        self._drop_device_arrays()

    def _drop_device_arrays(self) -> None:
        """Tear down the device tier's in-process state (the mirror stays
        authoritative).  Shared by the quarantine migration and the
        false-heal rollback — one copy of the teardown set."""
        with self._tier_lock:
            self._tier_epoch += 1   # fence any in-flight promotion
        self._leaves = None
        self._counts = None
        self._staging_pool = {}
        self._mirror = {}
        self._active_rows = None
        if self._pager is not None:
            self._pager.reset()

    def _forced_page_out(self) -> None:
        """Device-OOM pressure valve (monitor ``on_oom`` hook): spill the
        cold half of the resident rows so the retried dispatch has HBM
        headroom.  The current batch's rows stay protected — the in-flight
        flat scatter ids already reference them."""
        pager = self._pager
        if pager is None or self.pane_base is None:
            return
        rows, _gids = pager.resident_pairs()
        protected = getattr(self, "_active_rows", None)
        if protected is None:
            protected = np.empty(0, np.int64)
        evictable = int(rows.size) - int(protected.size)
        k = max(1, evictable // 2) if evictable > 0 else 0
        if k <= 0:
            return
        live = self._live_panes()
        victims = pager.pick_victims(k, protected)
        if victims.size == 0:
            return
        counts, leaves = self._gather_rows(victims, live)
        bits = self._mirror_bits_rows(victims, live)
        pager.spill_rows(victims, live, counts, leaves, bits)
        self._clear_mirror_rows(victims)

    def _maybe_repromote(self) -> bool:
        """Checkpoint-aligned safe point: if the process-wide monitor
        healed the device tier, re-promote this operator's state and leave
        degraded mode.  Returns True when a re-promotion happened."""
        if not self._degraded:
            return False
        from flink_tpu.runtime import device_health
        mon = device_health.get_monitor(create=False)
        if mon is None or not mon.healthy:
            return False
        self.flush_pipeline()

        def _promote():
            if self.emit_tier == "host":
                self._degraded = False   # device_refresh no-ops while degraded
                try:
                    self.device_refresh()  # stale replica: rebuild from mirror
                except BaseException:
                    self._degraded = True
                    raise
            else:
                self._repromote_device()   # device uploads only, no commits

        try:
            # GUARDED (with compile grace — the restore-path kernels
            # compile here): the healer's probe is one tiny dispatch, which
            # can read healthy while this operator's own lane still hangs
            # — a false heal must not hang the task thread mid-re-promotion
            mon.run_guarded(_promote, label=f"{self.name} re-promotion",
                            compile_grace=True)
        except DeviceQuarantinedError:
            # false heal: stay on the host tier (the mirror — dropped
            # only after a COMMITTED promotion — is still the authority);
            # the teardown bumps the tier epoch, fencing the abandoned
            # attempt out of ever committing
            self._degraded = True
            self._device_stale = True
            if self.emit_tier != "host":
                self._drop_device_arrays()
            else:
                with self._tier_lock:
                    self._tier_epoch += 1
            return False
        if self.emit_tier != "host":
            # COMMIT on the TASK thread, after the guarded upload
            # returned: an abandoned (hung) promotion attempt can never
            # flip the tier or drop the mirror behind our back
            self._degraded = False
            self._vmirror = {}
            self._device_stale = False
        self._repromotions += 1
        return True

    def _repromote_device(self) -> None:
        """Quarantine exit for the device tier, UPLOAD HALF: rebuild the
        device pane ring (and pager residency) from the host value mirror
        through the restore path.  Deliberately commits NO tier flags and
        keeps ``_vmirror`` — the caller (``_maybe_repromote``) commits on
        the task thread only after this guarded upload returned, and the
        device-state writes are FENCED on the tier epoch captured at
        entry: an abandoned attempt that later limps to completion finds
        the epoch advanced (by the false-heal rollback or a re-degrade)
        and aborts instead of landing stale state."""
        n = self.key_index.num_keys if self.key_index is not None else 0
        if n == 0 or self.pane_base is None:
            return
        with self._tier_lock:
            epoch = self._tier_epoch
        panes = self._live_panes()
        counts, leaves = self._mirror_columns(panes.tolist(), n)
        counts = np.asarray(counts)
        if self._pager is not None:
            with self._tier_lock:
                if epoch != self._tier_epoch:
                    raise DeviceQuarantinedError("re-promotion superseded")
                self._paged_restore_rows(n, panes, counts, leaves)
        else:
            slots = self._pane_slots(panes)
            with self._tier_lock:
                if epoch != self._tier_epoch:
                    raise DeviceQuarantinedError("re-promotion superseded")
                self._K = self._round_key_capacity(max(n, 1))
                self._ensure_alloc()
                self._set_columns(slots, leaves, counts)
                self._mirror = {}
                for j, p in enumerate(panes.tolist()):
                    nz = np.flatnonzero(counts[:, j] > 0)
                    if nz.size:
                        self._mirror_mark(int(p), nz)

    def device_health_stats(self) -> Dict[str, int]:
        """Per-operator tier-degradation counters (monitoring-grade, no
        pipeline barrier — same contract as ``paging_stats``)."""
        return {"degraded": int(self._degraded),
                "quarantine_migrations": self._quarantine_migrations,
                "repromotions": self._repromotions}

    # ------------------------------------------------------------------ time
    def _fired_horizon(self, now: int) -> int:
        """Largest window id whose maxTimestamp (= end-1) has been passed —
        the EventTimeTrigger fire condition.  Pure assigner math (no state
        reads), so the pipelined watermark fast-path may call it while hot
        stages are still in flight."""
        a = self.assigner
        denom = a.pane_stride * a.pane_ms
        w_max = (now + 1 - a._offset - a.panes_per_window * a.pane_ms) // denom
        while a.window_bounds(w_max + 1).max_timestamp <= now:
            w_max += 1
        while a.window_bounds(w_max).max_timestamp > now:
            w_max -= 1
        return w_max

    def process_watermark(self, watermark: Watermark) -> List[StreamElement]:
        self.watermark = max(self.watermark, watermark.timestamp)
        if (self._pipe_pending()
                and not self.async_fire
                and self.lateness == 0
                and self.trigger.fires_on_time and self.assigner.is_event_time
                and not isinstance(self.assigner, GlobalWindows)
                and self.last_fired_window is not None
                and self._fired_horizon(self.watermark)
                <= self.last_fired_window):
            # pipelined fast path: the watermark passed no new window end,
            # and with lateness 0 pane expiry coincides with fires — so
            # nothing fires, nothing expires, no state is read, and the
            # in-flight hot stages STAY in flight.  This is where the
            # pipeline's overlap comes from on per-batch-watermark drivers.
            return []
        self.flush_pipeline()
        if not (self.trigger.fires_on_time and self.assigner.is_event_time):
            # count triggers don't FIRE on time, but window state still
            # retires at window end + lateness (the reference registers
            # cleanup timers regardless of the trigger) — otherwise the
            # pane ring grows without bound
            if (self.trigger.fires_on_count
                    and not isinstance(self.assigner, GlobalWindows)
                    and self._leaves is not None
                    and self.pane_base is not None):
                self._expire_panes(self.watermark)
            return []
        return self._advance_time(self.watermark)

    def on_processing_time(self, timestamp_ms: int) -> List[StreamElement]:
        self._proc_time = max(self._proc_time, timestamp_ms)
        if self.assigner.is_event_time or not self.trigger.fires_on_time:
            return []
        return self._advance_time(self._proc_time)

    def end_input(self) -> List[StreamElement]:
        """Bounded input: fire everything outstanding (MAX_WATERMARK analog).

        GlobalWindows: EventTimeTrigger fires at MAX_WATERMARK (GlobalWindow
        maxTimestamp == Long.MAX_VALUE); NeverTrigger and partial count
        windows emit nothing — matching the reference, where a trailing
        partial countWindow is dropped at end of input."""
        if isinstance(self.assigner, GlobalWindows):
            self.flush_pipeline()
            pending = self.drain_pending_fires() if self.async_fire else []
            if self.trigger.fires_on_time:
                return pending + self._fire_by_count(force=True)
            return pending
        out = self._advance_time(2 ** 62)
        # a 2^62 watermark can START async fires in the same call: drain them
        if self.async_fire:
            out.extend(self.drain_pending_fires(force=True))
        return out

    def _now_ms(self) -> int:
        from flink_tpu.utils import clock

        return clock.now_ms()

    def _advance_time(self, now: int) -> List[StreamElement]:
        self.flush_pipeline()  # fires/expiry below read state
        # async fires from earlier calls surface before any new ones
        _pending = self.drain_pending_fires() if self.async_fire else []
        if self.pane_base is None or (self._leaves is None
                                      and not self._degraded):
            return _pending
        a = self.assigner
        if isinstance(a, GlobalWindows):  # no time-bounded panes to fire
            return _pending
        out: List[StreamElement] = list(_pending)
        # largest w whose maxTimestamp (= end-1) has been passed — the fire
        # condition of EventTimeTrigger: watermark >= window.maxTimestamp
        w_max = self._fired_horizon(now)
        # bound firing to windows that can contain data ([pane_base, max_pane])
        lo_window = a.windows_of_pane(self.pane_base)[0]
        hi_window = a.windows_of_pane(self.max_pane)[1]
        start = (self.last_fired_window + 1 if self.last_fired_window is not None
                 else lo_window)
        start = max(start, lo_window)
        for w in range(start, min(w_max, hi_window) + 1):
            out.extend(self._fire_window(w))
        if self.last_fired_window is None or w_max > self.last_fired_window:
            self.last_fired_window = w_max
        # ---- retention: clear panes whose last window end + lateness passed
        self._expire_panes(now)
        return out

    def _expire_panes(self, now: int):
        if self.pane_base is None:
            return
        # cleanup time = window.maxTimestamp + allowedLateness (reference:
        # WindowOperator.cleanupTime); a pane expires once its LAST covering
        # window's cleanup time has been passed by the watermark.
        expired = []
        p = self.pane_base
        while (p <= self.max_pane
               and self.assigner.last_window_end_of_pane(p) - 1 + self.lateness <= now):
            expired.append(p)
            p += 1
        if not expired:
            return
        self.pane_base = p
        if self.device_sync_mode == "deferred" or self._degraded \
                or self._leaves is None:
            # no in-line device writes while deferred/degraded: the next
            # device_refresh / re-promotion rebuilds the whole ring
            # (identity for slots without a live pane), subsuming this
            # clear
            self._device_stale = True
        else:
            # pane by pane: one program, however many expire at once
            for ep in expired:
                self._leaves, self._counts = self._clear_panes_step(
                    self._layout, self._leaves, self._counts,
                    self._pane_slots([ep]))
        for ep in expired:
            self._mirror.pop(ep, None)
            self._vmirror.pop(ep, None)
            if self._nm is not None:
                self._nm.drop_pane(ep)
        if self._pager is not None and not self._degraded:
            self._pager.drop_panes(expired)
        if self.pane_base > self.max_pane:
            self.max_pane = self.pane_base
        if self._count_baselines or self._value_baselines:
            # drop count-trigger registers of windows fully behind retention
            lo_w = self.assigner.windows_of_pane(self.pane_base)[0]
            for w in [w for w in self._count_baselines if w < lo_w]:
                del self._count_baselines[w]
                if self.incremental_state:
                    self._incr_cb_drops.add(w)
            for w in [w for w in self._value_baselines if w < lo_w]:
                del self._value_baselines[w]
                if self.incremental_state:
                    self._incr_vb_drops.add(w)

    # ------------------------------------------------------------------ fires
    def _fire_window(self, window_id: int) -> List[StreamElement]:
        with self._caused_by(
                window_end=int(self.assigner.window_bounds(window_id).end)):
            return self._fire_window_tier(window_id)

    def _fire_window_tier(self, window_id: int) -> List[StreamElement]:
        if self._degraded and self.emit_tier != "host":
            # quarantined device tier: serve the fire from the host value
            # mirror (zero device ops), the same pane combine the host
            # emit tier runs
            if self.pane_base is None:
                return []
            first, last = self.assigner.window_panes(window_id)
            if last < self.pane_base or first > self.max_pane:
                return []
            panes = np.arange(max(first, self.pane_base),
                              min(last, self.max_pane) + 1, dtype=np.int64)
            with self._phase("fire"):
                return self._fire_window_host(window_id, panes)
        if self._leaves is None:
            return []
        first, last = self.assigner.window_panes(window_id)
        # skip windows entirely outside retained panes
        if last < self.pane_base or first > self.max_pane:
            return []
        # mirror-indexed fires serve unsharded state AND sharded state whose
        # host-side mirrors are maintained (mesh host tier: the value mirror
        # is gid-indexed and mesh-size independent; mesh paged state: the
        # emit mirror + spill maps drive the gather/spilled fire)
        mirror_fire = self.key_index is not None and (
            self.sharding is None or self.emit_tier == "host"
            or self._pager is not None)
        if mirror_fire:
            # clip to retained panes: expired slots are identity on device,
            # and the mirror only tracks live panes anyway
            panes = np.arange(max(first, self.pane_base),
                              min(last, self.max_pane) + 1, dtype=np.int64)
            if self.emit_tier == "host":
                with self._phase("fire"):
                    return self._fire_window_host(window_id, panes)
            with self._phase("fire"):
                out = self._fire_window_gather(window_id, panes)
                if self._pager is not None:
                    # spilled keys are first-class in fires: their cells
                    # upload and run the same pane combine
                    out = out + self._fire_window_spilled(window_id, panes)
                return out
        # sharded device tier: no host mirror names the emit set, so the
        # dense step combines every key row's panes where they live and
        # the mask comes back with the values
        with self._phase("fire"):
            with self._phase("fire_dispatch"):
                panes = np.arange(first, last + 1, dtype=np.int64)
                rows = self._k_active()
                mask, result = self._fire_step(
                    self._layout, self._leaves, self._counts,
                    self._pane_slots(panes), rows)
            # key rows x pane columns x state arrays the step combines
            self.phase_bytes["fire_dense_cells"] = \
                self.phase_bytes.get("fire_dense_cells", 0) + \
                (rows or self._K) * panes.size * (len(self._leaves) + 1)
            return self._emit(mask, result,
                              self.assigner.window_bounds(window_id))

    def _fire_by_count(self, force: bool = False) -> List[StreamElement]:
        if self._leaves is None:
            return []
        thr = 1 if force else self.trigger.count_threshold
        ka = self._k_active() or self._K
        counts0 = self._layout.columns(
            self._counts, jnp.zeros((1,), jnp.int32), rows=ka)[:, 0]
        base = None
        if not force and not self.trigger.purges_on_fire:
            # FIRE-only trigger: state persists, so "n more elements" is
            # tracked by a baseline of already-fired counts per key
            counts_np = np.asarray(counts0, np.int64)
            base = self._count_baselines.get(0)
            if base is None or len(base) < ka:
                grown = np.zeros(ka, np.int64)
                if base is not None:
                    grown[:len(base)] = base
                base = grown
                self._count_baselines[0] = base
                if self.incremental_state:
                    # creation counts: a full snapshot packs the register
                    # even before its first fire
                    self._incr_cb_dirty.add(0)
            mask = jnp.asarray((counts_np - base[:ka]) >= thr)
        else:
            mask = counts0 >= thr
        if not bool(mask.any()):  # cheap pre-check: skip the K-wide assembly
            return []
        pane_slots = jnp.zeros((1,), jnp.int32)
        m, result = self._fire_step(self._layout, self._leaves,
                                    self._counts, pane_slots,
                                    self._k_active())
        mask = mask & m
        out = self._emit(mask, result, self.assigner.window_bounds(0))
        if base is not None:
            fired = np.asarray(mask)
            base[:ka] = np.where(fired, np.asarray(counts0, np.int64),
                                 base[:ka])
            if self.incremental_state:
                self._incr_cb_dirty.add(0)
        if self.trigger.purges_on_fire and out:
            full_mask = jnp.zeros((self._K,), bool).at[:ka].set(mask)
            self._leaves, self._counts = self._purge_keys_step(
                self._layout, self._leaves, self._counts, full_mask)
            fired_np = np.asarray(mask)
            for arr in self._mirror.values():  # whole key rows were purged
                arr[: fired_np.size][fired_np] = False
            if self.incremental_state:
                # purged rows are identity in EVERY retained pane now
                self._incr_mark_gids(np.flatnonzero(fired_np),
                                     self._live_panes())
        return out

    def _fire_count_in_panes(self, touched_panes) -> List[StreamElement]:
        """CountTrigger.onElement FIRE for time windows (tumbling: one pane
        per window): per touched pane, emit keys at/over the threshold, then
        purge those cells when the trigger purges."""
        if self.assigner.panes_per_window != 1 \
                or not self.trigger.purges_on_fire:
            # multi-pane windows and non-purging triggers both track fires
            # via per-(key, window) baselines instead of purging cells
            return self._fire_count_sliding(touched_panes)
        out: List[StreamElement] = []
        thr = self.trigger.count_threshold
        ka = self._k_active() or self._K
        for p in np.asarray(touched_panes).tolist():
            pane_slots = self._pane_slots([p])
            counts_col = np.asarray(self._layout.columns(
                self._counts, pane_slots, rows=ka))[:, 0]
            over = counts_col >= thr
            if not over.any():
                continue
            m, result = self._fire_step(self._layout, self._leaves,
                                        self._counts, pane_slots,
                                        self._k_active())
            mask = jnp.asarray(over) & m
            window = self.assigner.window_bounds(
                self.assigner.windows_of_pane(int(p))[0])
            out.extend(self._emit(mask, result, window))
            if self.trigger.purges_on_fire:
                full = jnp.zeros((self._K,), bool).at[:ka].set(mask)
                self._leaves, self._counts = self._purge_cells_step(
                    self._layout, self._leaves, self._counts, full,
                    pane_slots)
                fired_np = np.asarray(mask)
                marr = self._mirror.get(int(p))
                if marr is not None:
                    marr[: fired_np.size][fired_np] = False
                if self.incremental_state:
                    self._incr_mark_gids(np.flatnonzero(fired_np), [int(p)])
        return out

    def _fire_count_sliding(self, touched_panes) -> List[StreamElement]:
        """CountTrigger.onElement FIRE for SLIDING (multi-pane) windows: a
        (key, window) fires when the sum of the window's pane counts has
        grown by >= n since its last fire.  The per-window baseline is the
        CountTrigger count register (``ReducingState<Long>`` per (key,
        window) namespace in the reference) — it clears on FIRE.

        FIRE_AND_PURGE: overlapping windows share panes, so the purge is
        LOGICAL — a per-(key, window) VALUE baseline of the fired
        accumulator is kept, and emissions subtract it (invertible
        aggregates only, enforced at construction).  The emitted rows are
        exactly what the reference's per-namespace purged state would
        produce, without touching the shared pane cells."""
        out: List[StreamElement] = []
        thr = self.trigger.count_threshold
        purging = self.trigger.purges_on_fire
        ka = self._k_active() or self._K
        wins: set = set()
        for p in np.asarray(touched_panes).tolist():
            w0, w1 = self.assigner.windows_of_pane(int(p))
            wins.update(range(w0, w1 + 1))
        for w in sorted(wins):
            first, last = self.assigner.window_panes(w)
            lo, hi = max(first, self.pane_base), min(last, self.max_pane)
            if lo > hi:
                continue
            panes = np.arange(lo, hi + 1, dtype=np.int64)
            slots = self._pane_slots(panes)
            counts_w = np.asarray(
                self._layout.columns(self._counts, slots,
                                     rows=ka).sum(axis=1),
                dtype=np.int64)
            base = self._count_baselines.get(w)
            if base is None or len(base) < ka:
                grown = np.zeros(ka, np.int64)
                if base is not None:
                    grown[:len(base)] = base
                base = grown
            over = (counts_w - base[:ka]) >= thr
            if over.any():
                if purging:
                    out.extend(self._emit_purging_sliding(w, slots, ka,
                                                          over))
                else:
                    m, result = self._fire_step(self._layout, self._leaves,
                                                self._counts, slots,
                                                self._k_active())
                    mask = jnp.asarray(over) & m
                    out.extend(self._emit(mask, result,
                                          self.assigner.window_bounds(w)))
                base[:ka] = np.where(over, counts_w, base[:ka])
            self._count_baselines[w] = base
            if self.incremental_state:
                # the register exists (zero-grown included) — a full
                # snapshot would pack it, so the delta must ship it too
                self._incr_cb_dirty.add(w)
        return out

    def _emit_purging_sliding(self, w: int, slots, ka: int,
                              over: np.ndarray) -> List[StreamElement]:
        """One FIRE_AND_PURGE emission for sliding window ``w``: download
        the combined accumulator, subtract the value baseline (= contents
        already fired-and-purged), emit, advance the baseline for fired
        keys."""
        _m, combined = self._fire_acc_step(self._layout, self._leaves,
                                           self._counts, slots,
                                           self._k_active())
        comb_np = [np.asarray(l) for l in combined]
        self.phase_bytes["d2h"] = self.phase_bytes.get("d2h", 0) + \
            sum(l.nbytes for l in comb_np)
        vb = self._value_baselines.get(w)
        if vb is None or vb[0].shape[0] < ka:
            grown = [np.zeros_like(c) for c in comb_np]
            if vb is not None:
                for g, o in zip(grown, vb):
                    g[:o.shape[0]] = o
            vb = grown
        emit_leaves = tuple(c - b[:ka] for c, b in zip(comb_np, vb))
        result = self.agg.get_result(self.spec.unflatten(emit_leaves))
        out = self._emit(np.asarray(over),
                         result, self.assigner.window_bounds(w))
        for b, c in zip(vb, comb_np):
            sel = over.reshape((-1,) + (1,) * (b.ndim - 1))
            b[:ka] = np.where(sel, c, b[:ka])
        self._value_baselines[w] = vb
        if self.incremental_state:
            self._incr_vb_dirty.add(w)
        return out

    @partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2, 3))
    def _purge_cells_step(self, layout, leaves, counts, key_mask,
                          pane_slots):
        """Reset (key, pane) cells for fired count-trigger windows."""
        def purged(a, init):
            sel = layout.columns(a, pane_slots)
            m = key_mask.reshape((-1, 1) + (1,) * (sel.ndim - 2))
            return layout.set_columns(
                a, pane_slots, jnp.where(m, jnp.asarray(init, a.dtype), sel))

        new_leaves = tuple(purged(l, init)
                           for l, init in zip(leaves, self.spec.leaf_inits))
        return new_leaves, purged(counts, 0)

    def _emit(self, mask, result, window) -> List[StreamElement]:
        """Rows of a dense fire: the key rows ``mask`` marks, with their
        ``result`` values.  Both are whole-capacity device arrays (one
        block a device where the state is sharded)."""
        with self._phase("fire_d2h"):
            # blocks until the device has run every step queued ahead of
            # the fire step, the step, and the mask's copy to the host
            mask_np = np.asarray(mask)
        if self.key_index is not None:
            mask_np = mask_np[: self.key_index.num_keys]
        idx = np.nonzero(mask_np)[0]
        if idx.size == 0:
            return []
        leaves, treedef = jax.tree_util.tree_flatten(result)
        with self._phase("fire_d2h"):
            fetched = _fetch_collect(_fetch_enqueue(leaves))
        nbytes = mask_np.nbytes + sum(f.nbytes for f in fetched)
        self.phase_bytes["d2h"] = self.phase_bytes.get("d2h", 0) + nbytes
        self.phase_bytes["d2h_fire"] = \
            self.phase_bytes.get("d2h_fire", 0) + nbytes
        with self._phase("fire_assemble"):
            picked = jax.tree_util.tree_unflatten(
                treedef, [f[idx] for f in fetched])
            return self._rows_for(idx, picked, window)

    # ------------------------------------------------------------- paging
    def _live_panes(self) -> np.ndarray:
        return np.arange(self.pane_base, self.max_pane + 1, dtype=np.int64)

    def _page_slots(self, gids: np.ndarray) -> np.ndarray:
        """Map global key ids to resident HBM rows, evicting cold keys and
        promoting/initializing missing ones.  Batched: at most one page-out
        gather and one page-in scatter per micro-batch."""
        pager = self._pager
        pager.ensure_gids(self.key_index.num_keys)
        uniq = np.unique(gids)
        rows_u = pager.rows(uniq)
        missing = uniq[rows_u < 0]
        if missing.size:
            live = self._live_panes()
            n_evict = int(missing.size) - pager.free_count()
            if n_evict > 0:
                victims = pager.pick_victims(n_evict, rows_u[rows_u >= 0])
                counts, leaves = self._gather_rows(victims, live)
                bits = self._mirror_bits_rows(victims, live)
                pager.spill_rows(victims, live, counts, leaves, bits)
                self._clear_mirror_rows(victims)
            rows_new, recycled = pager.assign_rows(missing)
            if pager.any_spilled(missing, live):
                counts_cols, leaf_cols, bits, _found = pager.load_entries(
                    missing, live, delete=True)
                self._page_in(rows_new, live, counts_cols, leaf_cols)
                for j, p in enumerate(live.tolist()):
                    hit = bits[:, j]
                    if hit.any():
                        self._mirror_mark(int(p), rows_new[hit])
            elif recycled:
                # recycled rows carry the previous tenant's stale cells:
                # reset them even when nothing was promoted from spill
                self._reset_rows(rows_new)
        rows = pager.rows(gids)
        active = pager.rows(uniq)
        pager.touch(active)
        # rows referenced by the in-flight dispatch: protected from the
        # OOM forced page-out (their flat scatter ids are already built)
        self._active_rows = active
        return rows

    @partial(jax.jit, static_argnums=(0, 1))
    def _gather_rows_step(self, layout, leaves, counts, rows, pane_slots):
        """Page-out gather: the ``rows x pane_slots`` sub-grid —
        ``(counts[V, m], leaves[V, m, *leaf])``.  Pads may use any
        in-range id (callers slice them off host-side)."""
        return (layout.cells(counts, rows, pane_slots),
                tuple(layout.cells(l, rows, pane_slots) for l in leaves))

    @partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2, 3))
    def _page_in_step(self, layout, leaves, counts, rows, pane_slots,
                      counts_cols, leaf_cols):
        """Page-in: reset the target rows across the whole ring, then set
        their ``pane_slots`` columns from the promoted cells (identity
        where nothing was spilled).  Row pads = K, pane pads = P."""
        leaves, counts = self._reset_rows_core(layout, leaves, counts, rows)
        return (tuple(layout.set_cells(l, rows, pane_slots, col)
                      for l, col in zip(leaves, leaf_cols)),
                layout.set_cells(counts, rows, pane_slots, counts_cols))

    def _reset_rows_core(self, layout, leaves, counts, rows):
        """Reset whole key rows (every pane slot) to the accumulator
        identity.  Row pads use id K (dropped)."""
        return (tuple(layout.fill_rows(l, rows, init)
                      for l, init in zip(leaves, self.spec.leaf_inits)),
                layout.fill_rows(counts, rows, 0))

    @partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2, 3))
    def _reset_rows_step(self, layout, leaves, counts, rows):
        return self._reset_rows_core(layout, leaves, counts, rows)

    def _gather_rows(self, rows: np.ndarray, panes: np.ndarray):
        """Download the ``rows x panes`` cell grid (page-out / snapshot):
        (counts [V, m] np, leaves [V, m, *leaf] np)."""
        V, m = int(rows.size), int(panes.size)
        Vp = _next_pow2(V, 64)
        mp = _next_pow2(m, 1)
        rows_p = np.zeros(Vp, np.int32)
        rows_p[:V] = rows
        slots_p = np.zeros(mp, np.int32)
        slots_p[:m] = panes % self._P
        c, ls = self._gather_rows_step(self._layout, self._leaves,
                                       self._counts, jnp.asarray(rows_p),
                                       jnp.asarray(slots_p))
        counts = np.asarray(c)[:V, :m]
        leaves = [np.asarray(l)[:V, :m] for l in ls]
        self.phase_bytes["d2h_page_out"] = \
            self.phase_bytes.get("d2h_page_out", 0) + counts.nbytes + \
            sum(l.nbytes for l in leaves)
        return counts, leaves

    def _page_in(self, rows: np.ndarray, panes: np.ndarray,
                 counts_cols: np.ndarray, leaf_cols) -> None:
        """Upload promoted cells into freshly assigned rows (whole rows
        reset first — recycled rows carry the previous tenant's cells)."""
        R, m = int(rows.size), int(panes.size)
        Rp = _next_pow2(R, 64)
        mp = _next_pow2(m, 1)
        rows_p = np.full(Rp, self._K, np.int32)       # pads dropped
        rows_p[:R] = rows
        slots_p = np.full(mp, self._P, np.int32)      # pads dropped
        slots_p[:m] = panes % self._P
        cc = np.zeros((Rp, mp), np.int32)
        cc[:R, :m] = counts_cols
        lc = []
        for col, arr in zip(leaf_cols, identity_grid(self.spec, Rp, mp)):
            arr[:R, :m] = col
            lc.append(jnp.asarray(arr))
        self._leaves, self._counts = self._page_in_step(
            self._layout, self._leaves, self._counts, jnp.asarray(rows_p),
            jnp.asarray(slots_p), jnp.asarray(cc), tuple(lc))
        self.phase_bytes["h2d_page_in"] = \
            self.phase_bytes.get("h2d_page_in", 0) + cc.nbytes + \
            sum(int(l.nbytes) for l in lc)

    def _reset_rows(self, rows: np.ndarray) -> None:
        Rp = _next_pow2(int(rows.size), 64)
        rows_p = np.full(Rp, self._K, np.int32)
        rows_p[: rows.size] = rows
        self._leaves, self._counts = self._reset_rows_step(
            self._layout, self._leaves, self._counts, jnp.asarray(rows_p))

    def _mirror_bits_rows(self, rows: np.ndarray,
                          panes: np.ndarray) -> np.ndarray:
        """Emit-mirror bits of the ``rows x panes`` grid (spilled alongside
        counts so promotion restores the exact emit set)."""
        out = np.zeros((rows.size, panes.size), bool)
        for j, p in enumerate(panes.tolist()):
            arr = self._mirror.get(int(p))
            if arr is not None:
                out[:, j] = arr[rows]
        return out

    def _clear_mirror_rows(self, rows: np.ndarray) -> None:
        for arr in self._mirror.values():
            arr[rows] = False

    @partial(jax.jit, static_argnums=(0,))
    def _spill_fire_step(self, counts_cols, leaf_cols):
        """Window fire over UPLOADED spilled cells: the same pane combine +
        get_result the resident gather fire runs (same dtypes, same tree
        order over the same unpadded pane axis), so a key's emitted value
        is independent of which tier held it."""
        total = counts_cols.sum(axis=1)
        combined = combine_along_axis(leaf_cols, self.agg.combine_leaves,
                                      axis=1)
        result = self.agg.get_result(self.spec.unflatten(combined))
        return total > 0, result

    def _fire_window_spilled(self, window_id: int,
                             panes: np.ndarray) -> List[StreamElement]:
        """Fire contribution of COLD keys: load their spilled cells for the
        window's panes, upload as dense columns, combine on device,
        download only the emitted results.  Chunked so memory stays bounded
        at any spilled cardinality."""
        pager = self._pager
        gids = pager.spilled_gids(panes)
        if gids.size == 0:
            return []
        out: List[StreamElement] = []
        window = self.assigner.window_bounds(window_id)
        reverse = np.asarray(self.key_index.reverse_keys())
        CH = 1 << 14
        for lo in range(0, int(gids.size), CH):
            g = gids[lo: lo + CH]
            counts, leaves, _bits, _found = pager.load_entries(
                g, panes, delete=False)
            R, m = int(g.size), int(panes.size)
            Rp = _quantize_cap(R)
            cc = np.zeros((Rp, m), np.int32)
            cc[:R] = counts
            lc = []
            for col, arr in zip(leaves, identity_grid(self.spec, Rp, m)):
                arr[:R] = col
                lc.append(jnp.asarray(arr))
            mask, result = self._spill_fire_step(jnp.asarray(cc), tuple(lc))
            mask_np = np.asarray(mask)[:R]
            idx = np.flatnonzero(mask_np)
            if idx.size == 0:
                continue
            res_np = jax.tree_util.tree_map(
                lambda a: np.asarray(a)[:R][idx], result)
            self.phase_bytes["d2h"] = self.phase_bytes.get("d2h", 0) + \
                mask_np.nbytes + sum(a.nbytes for a in
                                     jax.tree_util.tree_leaves(res_np))
            out.extend(self._rows_for_keys(reverse[g[idx]], res_np, window))
        return out

    def paging_stats(self) -> Optional[Dict[str, int]]:
        """Occupancy + eviction/promotion counters, or None when paging is
        off (job-scope ``paging.*`` metrics and bench details read this).

        Monitoring-grade: deliberately NO pipeline barrier — metrics/REST
        pollers call this from foreign threads and must neither block on
        in-flight hot stages nor receive the task's parked stage error.
        Under pipelining the counters may lag by the (bounded) in-flight
        stages; every correctness path (fires, snapshots) barriers."""
        if self._pager is None:
            return None
        n = self.key_index.num_keys if self.key_index is not None else 0
        return self._pager.stats(n)

    # ------------------------------------ incremental (delta) checkpoints
    def _incr_clear(self) -> None:
        """Reset ALL delta tracking: the next cut must be a full re-base
        (restore, reset — any point where the confirmed-base linkage to
        the storage-side increment chain is severed)."""
        self._incr_keychunks: List = []       # live (raw keys, panes) pairs
        self._incr_gid_cells: Dict[int, List[np.ndarray]] = {}  # pane->gids
        self._incr_cb_dirty: set = set()
        self._incr_vb_dirty: set = set()
        self._incr_cb_drops: set = set()
        self._incr_vb_drops: set = set()
        #: cuts taken but not yet confirmed: [(cid, cells, cbd, vbd,
        #: cb_drops, vb_drops, num_keys_at_cut)] — every later cut ships
        #: the UNION of these with the live dirt, so a crash between cut
        #: and confirmation can never lose a mutation
        self._incr_unconfirmed: List = []
        self._incr_last_confirmed: Optional[int] = None
        self._incr_confirmed_n = 0

    def _incr_mark_batch(self, keys: np.ndarray, panes: np.ndarray) -> None:
        self._incr_keychunks.append((np.array(keys, copy=True),
                                     np.array(panes, copy=True)))
        if len(self._incr_keychunks) > 512:
            # bound live memory between cuts: coalesce into the gid map
            self._incr_coalesce_live()

    def _incr_mark_gids(self, gids: np.ndarray, panes) -> None:
        """Product mark: every (gid, pane) cell in gids x panes is dirty."""
        if not self.incremental_state or len(gids) == 0:
            return
        g = np.asarray(gids, np.int64).copy()
        for p in np.asarray(panes).tolist():
            self._incr_gid_cells.setdefault(int(p), []).append(g)

    def _incr_coalesce_live(self) -> None:
        """Resolve live raw-key chunks to gids and fold into the cell map."""
        chunks, self._incr_keychunks = self._incr_keychunks, []
        if self.key_index is None:
            return
        for keys, panes in chunks:
            gids = np.asarray(self.key_index.lookup(keys), np.int64)
            ok = gids >= 0
            if not ok.all():
                gids, panes = gids[ok], panes[ok]
            for p in np.unique(panes).tolist():
                self._incr_gid_cells.setdefault(int(p), []).append(
                    gids[panes == p])

    def _incr_freeze(self, cid: int) -> None:
        """Move the live dirt into the unconfirmed ledger under ``cid``."""
        self._incr_coalesce_live()
        cells = {p: np.unique(lst[0] if len(lst) == 1
                              else np.concatenate(lst))
                 for p, lst in self._incr_gid_cells.items()}
        n = self.key_index.num_keys if self.key_index is not None else 0
        self._incr_unconfirmed.append(
            (cid, cells, self._incr_cb_dirty, self._incr_vb_dirty,
             self._incr_cb_drops, self._incr_vb_drops, n))
        self._incr_gid_cells = {}
        self._incr_cb_dirty, self._incr_vb_dirty = set(), set()
        self._incr_cb_drops, self._incr_vb_drops = set(), set()

    def _incremental_snapshot(self, cid: int):
        """A ``window_delta`` increment covering every mutation since the
        last CONFIRMED checkpoint, or None when this cut must be a full
        re-base (no confirmed base yet, or the grid is too dirty for a
        delta to pay off).  Either way the live dirt is frozen under
        ``cid`` so the NEXT cut keeps covering it until confirmation."""
        self._incr_freeze(cid)
        base_n = self._incr_confirmed_n
        if self._incr_last_confirmed is None or self.key_index is None:
            return None
        n = self.key_index.num_keys
        # union of all unconfirmed dirt (absolute values: last-writer-wins
        # replay makes shipping a superset harmless)
        union: Dict[int, List[np.ndarray]] = {}
        cbd: set = set()
        vbd: set = set()
        cb_drops: set = set()
        vb_drops: set = set()
        for (_c, ecells, ecbd, evbd, ecbdrop, evbdrop, _n) \
                in self._incr_unconfirmed:
            for p, g in ecells.items():
                union.setdefault(int(p), []).append(g)
            cbd |= ecbd
            vbd |= evbd
            cb_drops |= ecbdrop
            vb_drops |= evbdrop
        cells_map: Dict[int, np.ndarray] = {}
        for p, lst in union.items():
            if self.pane_base is not None and \
                    not (self.pane_base <= p <= self.max_pane):
                continue            # pane expired since it was marked
            g = lst[0] if len(lst) == 1 else np.unique(np.concatenate(lst))
            g = np.asarray(g, np.int64)
            g = g[g < n]
            if g.size:
                cells_map[int(p)] = g
        has_grid = (self._leaves is not None or self._degraded) \
            and self.pane_base is not None
        if has_grid:
            m = int(self.max_pane - self.pane_base + 1)
            dirty_cells = sum(int(g.size) for g in cells_map.values())
            if n and m and dirty_cells > self.incr_rebase_ratio * n * m:
                return None          # too dirty: re-base with a full cut
        inc: Dict[str, Any] = {
            "__increment__": 1, "kind": "window_delta",
            "checkpoint_id": cid, "n": n, "base_n": base_n,
            "has_grid": has_grid,
            "meta": {"pane_base": self.pane_base, "max_pane": self.max_pane,
                     "last_fired_window": self.last_fired_window,
                     "watermark": self.watermark,
                     "late_dropped": self.late_dropped, "P": self._P},
            "key_index_kind": type(self.key_index).__name__,
            "key_tail": np.asarray(
                self.key_index.reverse_keys()[base_n:n]).copy(),
        }
        cell_list: List[Dict[str, Any]] = []
        if has_grid and cells_map:
            dirty_panes = sorted(cells_map)
            panes_arr = np.asarray(dirty_panes, np.int64)
            if self.snapshot_source == "mirror" or self._degraded:
                with self._phase("snapshot"):
                    counts, leaves = self._mirror_columns(dirty_panes, n)
                for j, p in enumerate(dirty_panes):
                    g = cells_map[p]
                    cell_list.append(
                        {"pane": p, "gids": g,
                         "counts": counts[g, j].copy(),
                         "leaves": [l[g, j].copy() for l in leaves]})
            elif self._pager is not None:
                with self._phase("snapshot"):
                    counts, leaves = self._paged_snapshot_rows(n, panes_arr)
                for j, p in enumerate(dirty_panes):
                    g = cells_map[p]
                    cell_list.append(
                        {"pane": p, "gids": g,
                         "counts": counts[g, j].copy(),
                         "leaves": [l[g, j].copy() for l in leaves]})
            else:
                # device tier: ONE gather of the dirty-rows x dirty-panes
                # grid — d2h bytes scale with the dirt, not the state
                rows = np.unique(np.concatenate(
                    [cells_map[p] for p in dirty_panes]))
                with self._phase("snapshot"):
                    counts, leaves = self._gather_rows(rows, panes_arr)
                for j, p in enumerate(dirty_panes):
                    g = cells_map[p]
                    idx = np.searchsorted(rows, g)
                    cell_list.append(
                        {"pane": p, "gids": g,
                         "counts": counts[idx, j].copy(),
                         "leaves": [l[idx, j].copy() for l in leaves]})
        inc["cells"] = cell_list
        if has_grid:
            from flink_tpu.state.evolution import acc_leaf_schema
            inc["leaf_meta"] = [
                (np.asarray(init, np.dtype(d)), str(np.dtype(d)),
                 tuple(shape))
                for init, shape, d in zip(self.spec.leaf_inits,
                                          self.spec.leaf_shapes,
                                          self.spec.leaf_dtypes)]
            inc["leaf_schema"] = acc_leaf_schema(self.spec)
        else:
            inc["leaf_meta"] = []
        if self._pager is not None:
            inc["paging_stats"] = self._pager.stats(n)
        cb_vals: Dict[int, np.ndarray] = {}
        for w in cbd:
            b = self._count_baselines.get(w)
            if b is None:
                cb_drops.add(w)
            else:
                cb_vals[w] = np.asarray(b, np.int64).copy()
        vb_vals: Dict[int, List[np.ndarray]] = {}
        for w in vbd:
            ls = self._value_baselines.get(w)
            if ls is None:
                vb_drops.add(w)
            else:
                vb_vals[w] = [np.asarray(l).copy() for l in ls]
        inc["count_baselines"] = cb_vals
        inc["value_baselines"] = vb_vals
        inc["cb_drops"] = sorted(cb_drops)
        inc["vb_drops"] = sorted(vb_drops)
        return inc

    def notify_checkpoint_complete(self, checkpoint_id: int) -> None:
        """Track the last completed checkpoint so queryable live views tag
        the consistency point they reflect (base hook is a no-op)."""
        if self._last_completed_checkpoint is None \
                or checkpoint_id > self._last_completed_checkpoint:
            self._last_completed_checkpoint = checkpoint_id
        # delta tracking: dirt up to a CONFIRMED cut may be forgotten —
        # only for cuts we actually froze (savepoints/finals are not part
        # of the storage-side increment chain and must not advance it)
        match = next((e for e in self._incr_unconfirmed
                      if e[0] == checkpoint_id), None)
        if match is not None:
            self._incr_unconfirmed = [e for e in self._incr_unconfirmed
                                      if e[0] > checkpoint_id]
            self._incr_last_confirmed = checkpoint_id
            self._incr_confirmed_n = match[6]
        super().notify_checkpoint_complete(checkpoint_id)

    def queryable_view(self):
        """The live-read view (``queryable/view.WindowReadView``) when this
        operator was constructed with ``queryable=<name>``, else None.
        Monitoring-grade: reading it takes no barrier."""
        return self._qview

    def close(self) -> None:
        try:
            self.flush_pipeline()
        finally:
            if self._pipe is not None:
                self._pipe.close()
                self._pipe = None
            if self._pager is not None:
                self._pager.close()

    def _paged_snapshot_rows(self, n: int, panes: np.ndarray):
        """Dense gid-indexed snapshot arrays merging both tiers:
        counts int32 [n, m] + one [n, m, *leaf] per ACC leaf."""
        m = int(panes.size)
        counts = np.zeros((n, m), np.int32)
        leaves = identity_grid(self.spec, n, m)
        rows, gids = self._pager.resident_pairs()
        if rows.size:
            res_counts, res_leaves = self._gather_rows(rows, panes)
            counts[gids] = res_counts
            for dst, src in zip(leaves, res_leaves):
                dst[gids] = src
        self._pager.fill_snapshot(counts, leaves, panes)
        return counts, leaves

    def _paged_restore_rows(self, n: int, panes: np.ndarray,
                            counts_np: np.ndarray, leaves_np) -> None:
        """Restore a dense snapshot at THIS operator's K_cap: the first
        ``min(n, K_cap)`` keys become resident (one upload), the overflow
        pages straight into the spill tier — a savepoint written at any
        capacity (paged or fully resident) restores at any other."""
        pager = self._pager
        pager.reset()
        pager.ensure_gids(max(n, 1))
        self._ensure_alloc()
        R = min(n, self._K)
        self._mirror = {}
        if R:
            # fresh rows are 0..R-1 in gid order: upload via a plain
            # slice-set, so resident row i == global id i after restore
            pager.assign_rows(np.arange(R, dtype=np.int64))
            slots = self._pane_slots(panes)
            self._set_columns(slots, [s[:R] for s in leaves_np],
                              counts_np[:R])
            for j, p in enumerate(panes.tolist()):
                nz = np.flatnonzero(counts_np[:R, j] > 0)
                if nz.size:
                    self._mirror_mark(int(p), nz)
        if n > R:
            pager.import_rows(np.arange(R, n, dtype=np.int64), panes,
                              counts_np, [np.asarray(l) for l in leaves_np])

    # ------------------------------------------------------------- snapshots
    def prepare_snapshot_pre_barrier(self) -> List[StreamElement]:
        """Drain pending async fire downloads so their emissions travel
        downstream BEFORE the barrier — the reference drains its external
        Python runtime the same way
        (``AbstractPythonFunctionOperator.prepareSnapshotPreBarrier:173``).
        After this, ``snapshot_state`` is always legal, async_fire included.

        Also the checkpoint-aligned SAFE POINT for device-lane healing:
        a degraded operator whose monitor probed healthy re-promotes its
        state to the device tier here, so the snapshot that follows is
        already device-sourced and the tier switch is barrier-aligned —
        a watermark or barrier can never observe half-migrated state."""
        self.flush_pipeline()
        self._maybe_repromote()
        if self.async_fire:
            return self.drain_pending_fires(force=True)
        return []

    def snapshot_state(self) -> Dict[str, Any]:
        cid = current_checkpoint_id()
        with self._caused_by(checkpoint=cid):   # None for a final snapshot
            return self._snapshot_state(cid)

    def _snapshot_state(self, cid: Optional[int]) -> Dict[str, Any]:
        self.flush_pipeline()  # the snapshot must contain in-flight stages
        if self._pending_fires:
            # the runtime must call prepare_snapshot_pre_barrier first (all
            # in-repo runtimes do); a snapshot with un-drained async fires
            # could neither replay nor contain those emissions — refuse
            raise ValueError(
                "snapshot with in-flight async fires: the runtime must call "
                "prepare_snapshot_pre_barrier() (and forward its elements) "
                "before snapshot_state()")
        if self.incremental_state and cid is not None \
                and snapshot_is_incremental():
            inc = self._incremental_snapshot(cid)
            if inc is not None:
                return inc
            # fall through: full re-base cut (the dirt was still frozen
            # under cid, so confirmation advances the delta base to it)
        snap: Dict[str, Any] = {
            "pane_base": self.pane_base,
            "max_pane": self.max_pane,
            "last_fired_window": self.last_fired_window,
            "watermark": self.watermark,
            "late_dropped": self.late_dropped,
            "P": self._P,
        }
        if self.key_index is not None:
            snap["key_index"] = self.key_index.snapshot()
            snap["key_index_kind"] = type(self.key_index).__name__
        if (self._leaves is not None or self._degraded) \
                and self.pane_base is not None and self.key_index is not None:
            n = self.key_index.num_keys
            panes = np.arange(self.pane_base, self.max_pane + 1, dtype=np.int64)
            snap["panes"] = panes
            if self.snapshot_source == "mirror" or self._degraded:
                # degraded: the host value mirror IS the state — the dense
                # gid-indexed format is identical, so a checkpoint taken
                # DURING quarantine restores on either tier
                # serialize the host mirror (continuously equal to device
                # state, in higher precision) — zero device->host transfer;
                # cast down to the device leaf dtypes so the snapshot format
                # is identical either way
                with self._phase("snapshot"):
                    with self._phase("snapshot_assemble"):
                        counts, leaves = self._mirror_columns(
                            panes.tolist(), n)
                    snap["leaves"] = leaves
                    snap["counts"] = counts
            elif self._pager is not None:
                # paged: resident rows download in one gather, spilled rows
                # fill in from the store — the snapshot is the SAME dense
                # gid-indexed format either way, so redistribute/rescale
                # and restore into a non-paged operator work unchanged
                with self._phase("snapshot"):
                    snap["counts"], snap["leaves"] = \
                        self._paged_snapshot_rows(n, panes)
            else:
                # snapshot only live keys × live panes (device→host transfer)
                with self._phase("snapshot"):
                    *snap["leaves"], snap["counts"] = \
                        self._read_columns(panes, n)
                nbytes = snap["counts"].nbytes + \
                    sum(l.nbytes for l in snap["leaves"])
                self.phase_bytes["d2h"] = \
                    self.phase_bytes.get("d2h", 0) + nbytes
                self.phase_bytes["d2h_snapshot"] = \
                    self.phase_bytes.get("d2h_snapshot", 0) + nbytes
            from flink_tpu.state.evolution import acc_leaf_schema
            snap["leaf_schema"] = acc_leaf_schema(self.spec)
        if self._pager is not None:
            snap["paging_stats"] = self._pager.stats(
                self.key_index.num_keys if self.key_index else 0)
        if self._count_baselines:
            n = self.key_index.num_keys if self.key_index else 0
            packed = {}
            for w, b in self._count_baselines.items():
                arr = np.zeros(n, np.int64)  # pad: slot-aligned with leaves
                arr[:min(len(b), n)] = np.asarray(b)[:n]
                packed[w] = arr
            snap["count_baselines"] = packed
        if self._value_baselines:
            snap["value_baselines"] = {
                w: [np.asarray(l).copy() for l in leaves]
                for w, leaves in self._value_baselines.items()}
        return snap

    def restore_state(self, snap: Dict[str, Any]) -> None:
        self.flush_pipeline()
        # mesh snapshots arrive as per-shard slices with key-group-range
        # manifests (state/shard_layout): merge to the dense gid-indexed
        # layout first — restore at ANY mesh size (1 included) re-slices
        # by the CURRENT operator's layout, not the writer's
        from flink_tpu.state.shard_layout import densify_keyed_snapshot
        snap = densify_keyed_snapshot(snap)
        # restores land on the device tier; if the process-wide monitor is
        # still quarantined, the first dispatch re-quarantines and the
        # operator migrates again (the snapshot format is tier-agnostic)
        self._degraded = False
        with self._tier_lock:
            self._tier_epoch += 1   # fence any in-flight promotion
        self._active_rows = None
        self.pane_base = snap["pane_base"]
        self.max_pane = snap["max_pane"]
        self.last_fired_window = snap["last_fired_window"]
        self.watermark = snap["watermark"]
        self.late_dropped = snap.get("late_dropped", 0)
        self._P = snap["P"]
        self._nm = None          # rebinds to the restored key index below
        self._nm_tried = False
        self._incr_clear()       # restored state: first cut is a full base
        if "key_index" in snap:
            if snap["key_index_kind"] == "ObjectKeyIndex":
                self.key_index = ObjectKeyIndex.restore(snap["key_index"])
            else:
                self.key_index = KeyIndex.restore(snap["key_index"])
            self._K = self._round_key_capacity(max(self.key_index.num_keys, 1))
            self._try_native_mirror()
        self._leaves = None
        self._counts = None
        self._mirror = {}
        if self._pager is not None:
            self._pager.reset()
        if "leaves" in snap:
            from flink_tpu.state.evolution import migrate_acc_leaves
            n = snap["counts"].shape[0]
            panes = np.asarray(snap["panes"], np.int64)

            def fill(j, _n=n, _np=len(panes)):
                # ADDED accumulator field: identity rows in [n, panes] shape
                init = np.asarray(self.spec.leaf_inits[j],
                                  self.spec.leaf_dtypes[j])
                return np.broadcast_to(
                    init, (_n, _np) + tuple(self.spec.leaf_shapes[j])).copy()

            leaves = migrate_acc_leaves(snap["leaves"],
                                        snap.get("leaf_schema"),
                                        self.spec, fill)
            if self._pager is not None:
                # paged restore: resident prefix uploads, overflow spills —
                # works at ANY K_cap relative to the snapshot's key count
                self._paged_restore_rows(n, panes, np.asarray(snap["counts"]),
                                         leaves)
                self._vmirror = {}
                self._count_baselines = {}
                self._value_baselines = {}
                return
            # resolve the cadence NOW (a process-wide calibration verdict may
            # already exist): a deferred restore skips the dispatched device
            # import — the costliest possible upload on exactly the links
            # deferred mode exists for ("calibrating" restores like scatter)
            if self._resolve_device_sync() == "deferred":
                # the mirror (rebuilt below) is the authority; the device
                # replica catches up at the next device_refresh.  Alloc so
                # time/fire guards see live state (content = identity).
                self._ensure_alloc()
                self._device_stale = True
            else:
                self._ensure_alloc()
                slots = self._pane_slots(panes)
                self._set_columns(slots, leaves, snap["counts"])
            # rebuild the host emit mirror from the snapshot's counts
            self._mirror = {}
            counts_np = np.asarray(snap["counts"])
            for j, p in enumerate(panes.tolist()):
                nz = np.flatnonzero(counts_np[:, j] > 0)
                if nz.size:
                    self._mirror_mark(int(p), nz)
            # host tier: re-seed the value mirror from the snapshot (device
            # precision — the f64 surplus re-accumulates from here on)
            self._vmirror = {}
            if self.emit_tier == "host":
                restored = [np.asarray(l) for l in leaves]
                for j, p in enumerate(panes.tolist()):
                    if not counts_np[:, j].any():
                        continue
                    if self._nm is not None:
                        self._nm.import_pane(
                            int(p), counts_np[:, j],
                            [src[:, j] for src in restored])
                        continue
                    entry = self._vmirror_pane(int(p))
                    entry[0][:n] = counts_np[:, j]
                    for k, src in enumerate(restored):
                        entry[k + 1][:n] = src[:, j].astype(
                            self._mirror_dtypes[k])
        self._count_baselines = {w: np.asarray(b, np.int64).copy()
                                 for w, b in
                                 snap.get("count_baselines", {}).items()}
        self._value_baselines = {w: [np.asarray(l).copy() for l in leaves]
                                 for w, leaves in
                                 snap.get("value_baselines", {}).items()}

