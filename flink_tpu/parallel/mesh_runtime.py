"""Mesh-sharded job runtime: the keyed exchange IS the execution path.

This module fuses the device data plane into the normal job runtime: a
``MeshWindowAggOperator`` is a drop-in ``WindowAggOperator`` whose micro-batch
step runs under ``shard_map`` over a ``jax.sharding.Mesh`` — records are
row-split over the devices (as a distributed source would produce them), an
``all_to_all`` collective re-keys each record to the device owning its key
group, and the owning device folds it into its LOCAL state shard.  This is
the TPU-native analog of the reference's keyed exchange being the runtime
(``KeyGroupStreamPartitioner.java`` + the Netty stack,
``NettyMessage.java:254``) rather than a detached demo: any
``env.execute()``-submitted windowed pipeline runs through it when the
environment is given a mesh (``StreamExecutionEnvironment(mesh=...)``).

Design notes (TPU-first):
- **No overflow, no flow-control sync in the hot loop.**  The host assigns
  dense key slots (the record-serializer role), and a record's destination
  shard is a function of its slot, so the per-``(src, dest)`` bucket
  capacity is KNOWN before dispatch; the exchange compiles at a quantized
  capacity that always fits.  The destination itself is derived on the
  device, where the id already is: the host ships the staged flat ids and
  value columns packed into one array of 4-byte words, one transfer a
  device.  The general case with capacity renegotiation
  lives in ``parallel/exchange.py`` (``ResizingExchange``).
- **One jitted step per micro-batch**: bucket → ``all_to_all`` (ICI) →
  local scatter-combine, all inside one ``shard_map`` — XLA overlaps the
  collective with the scatter epilogue.
- **State is globally addressed.**  Key slot ids are global ``[0, K)``;
  device ``d`` owns rows ``[d*K/D, (d+1)*K/D)``, the contiguous key-group
  ranges of ``KeyGroupRangeAssignment.java:50-84``.  Snapshots are therefore
  mesh-size-independent: a snapshot taken on 8 devices restores onto 4 (or
  1) unchanged — the key-group rescaling story
  (``StateAssignmentOperation.reDistributeKeyedStates``).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flink_tpu.operators.session_window import SessionWindowOperator
from flink_tpu.operators.window_agg import (WindowAggOperator, _PAD_ID,
                                            _Staging, _next_pow2)
from flink_tpu.ops.pane_layout import ShardRing
from flink_tpu.parallel.mesh import KG_AXIS, make_mesh, state_sharding


from flink_tpu.ops.shapes import quantize_pow2


def _quantize(n: int, floor: int = 16) -> int:
    """pow2/4-step rounding: bounded compile count, <=25% padding."""
    return quantize_pow2(n, floor=floor, steps=4)


class _PackedBatch(_Staging):
    """One reusable packed upload buffer of the mesh operator: ``words``
    is ``[D, W, block]`` 4-byte words, device ``d``'s share (its block of
    the flat ids, then of each packed leaf) contiguous at ``words[d]``.
    Reuse is gated by ``_Staging``'s rule: ``token`` is an output of the
    step that consumed the buffer, so a backend that aliases host memory
    into the transfer never sees the next batch's rows."""

    __slots__ = ("words",)

    def __init__(self, shape):
        self.words = np.empty(shape, np.int32)
        self.token = None


class MeshWindowAggOperator(WindowAggOperator):
    """``WindowAggOperator`` executing as ONE logical SPMD operator over a
    1-D key-group mesh: state sharded by key group, records re-keyed over
    ICI via ``all_to_all`` inside the update step.  API-compatible with the
    single-chip operator — graph translation swaps it in when the
    environment carries a mesh.

    Per-shard subsystems (ISSUE 6): the host emit tier, cold-key paging,
    and the degraded-tier migration all run against the SAME key-group-
    range layout the device state uses (``state/shard_layout.ShardLayout``):

    - **host tier**: the fused native probe/mirror pass shards by
      CONTIGUOUS slot range (``shard_div = K / D``), so probe shard ``t``
      maintains exactly the mirror rows whose device block lives on mesh
      device ``t`` — the probe_mirror wall becomes D independent, smaller
      probes (per-shard wall times in ``phase_shard_ns``), and the staging
      buffer each probe fills feeds the sharded scatter directly.
    - **paging**: the (host-side) ``DevicePager`` runs unchanged over
      global HBM rows; a record's destination shard is its resident row's
      owning block, so page-in/page-out gathers and the spilled-key fire
      are mesh-size independent (and digests stay bit-identical at any D).
    - **degraded tier**: a process-wide device quarantine degrades the
      WHOLE mesh — the live pane ring materializes shard-by-shard through
      the dense snapshot path into the host value mirror, fires continue
      bit-exactly from numpy, and re-promotion at the checkpoint-aligned
      safe point rebuilds the sharded state.
    - **snapshots** are per-shard slices with key-group-range manifests
      (``state/shard_layout.split_to_shard_slices``); restore at any mesh
      size (single-chip included) re-slices by the reader's layout.

    The state is held as the fold scatters into it
    (``ops/pane_layout.ShardRing``): every state array is ONE 1-D array
    ``[D * P * K/D, *leaf]`` sharded over ``KG_AXIS`` on axis 0, so device
    ``d``'s block is the pane-major ring ``PaneRing(K/D, P)`` of the key
    rows it owns, and the step touches it with one in-place scatter on the
    donated block: no flatten, no copy.  Fires, cuts and restores read and
    write ``[K, m]`` pane columns in global key order through the layout,
    so the snapshot format does not know how a block is held.

    Chained dispatches stay pre-partitioned end-to-end: state flows out of
    the ``shard_map`` step with ``out_specs == in_specs`` (a device's ring
    block on ``KG_AXIS``), a batch is the base class's staged ``(flat_ids,
    *values)`` packed into ONE 1-D array of 4-byte words split onto the
    same axis (a device's share: its block of the ids, then of each
    leaf; a leaf that is not 4 bytes wide on the device rides beside it),
    so a batch costs one transfer a device whatever the aggregate's
    column count (the flat id and the value columns ride the exchange;
    slot, pane and destination are derived from the id on the device),
    and nothing in between reshards — one XLA compile per
    (mesh size, K_cap, batch geometry), asserted by the tier-1 smoke via
    :meth:`mesh_step_cache_size`.
    """

    _SHARDED_HOST_TIER = True
    _SHARDED_PAGING = True
    _SHARDED_DEGRADE = True

    def __init__(self, *args, mesh: Optional[Mesh] = None,
                 n_devices: Optional[int] = None, **kwargs):
        if mesh is None:
            mesh = make_mesh(n_devices)
        self.mesh = mesh
        self.n_shards = int(mesh.devices.size)
        kwargs.setdefault("sharding", state_sharding(mesh))
        super().__init__(*args, **kwargs)
        #: row sharding for the incoming batch (split over devices like a
        #: distributed source's partitions)
        self._row_sharding = NamedSharding(mesh, P(KG_AXIS))
        #: per-shard probe timing buffer (phase_shard_ns feed)
        self._shard_ns_buf = np.zeros(self.n_shards, np.int64)
        #: sticky high-water of the exchange's bucket capacity
        self._exchange_cap_hw = 0
        #: reusable packed upload buffers by shape (``_route_batch``)
        self._packed_pool = {}

    # ---------------------------------------------------------------- layout
    @property
    def _layout(self):
        """One pane-major ring per device (``ops/pane_layout.py``)."""
        return ShardRing(self._K, self._P, self.mesh)

    def shard_layout(self):
        """The key-group-range state layout (shared by snapshots, the
        sharded probe, and the record router)."""
        from flink_tpu.parallel.mesh import layout_for
        return layout_for(self.mesh, self._K)

    def _probe_shards(self):
        """Align the fused native probe with the mesh: by default one probe
        shard per device, owning the contiguous slot range
        [t*K/D, (t+1)*K/D) — the rows whose device state block lives on
        mesh device t.  The ownership divisor derives from the ACTUAL probe
        shard count (an explicit ``native_shards`` override, or the native
        pool's 16-shard cap on very wide meshes), so the ranges stay
        balanced when S != D; the last range is open-ended either way.
        The timing buffer feeds the per-shard probe_mirror breakdown."""
        S = min(self.native_shards or self.n_shards, 16)  # C pool cap
        if self._shard_ns_buf.size < S:
            self._shard_ns_buf = np.zeros(S, np.int64)
        return S, -(-self._K // S), self._shard_ns_buf

    def mesh_step_cache_size(self) -> int:
        """Compiled-variant count of the sharded update step (the tier-1
        recompile smoke: one batch geometry must compile exactly once —
        an implicit reshard would mint a second cache entry)."""
        fn = type(self)._mesh_update_step
        try:
            return int(fn._cache_size())
        except Exception:  # noqa: BLE001 — jax without the cache probe
            return -1

    # ------------------------------------------------------------- snapshots
    def snapshot_state(self):
        """Per-shard slices with key-group-range manifests instead of one
        dense array set (the dense layout is recovered by
        ``densify_keyed_snapshot`` on restore/rescale, so every consumer of
        the old format keeps working)."""
        snap = super().snapshot_state()
        # paged snapshots stay dense: their gid space exceeds K_cap and is
        # residency-independent — row-block ownership does not decompose it
        # (the spill store is the per-shard story there)
        if "counts" in snap and self._pager is None:
            from flink_tpu.state.shard_layout import split_to_shard_slices
            mp = getattr(getattr(self, "ctx", None), "max_parallelism", 128)
            snap = split_to_shard_slices(snap, self.shard_layout(), mp)
        return snap

    # ------------------------------------------------------------- device op
    @partial(jax.jit, static_argnums=(0, 1, 4, 5), donate_argnums=(2,))
    def _mesh_update_step(self, layout, leaves_counts, batch, cap: int,
                          cols: tuple):
        """One sharded micro-batch into the state: unpack this device's
        columns → bucket by destination → ``all_to_all`` over ICI →
        scatter-combine into the local block.  ``batch`` = (packed,
        *beside), each split over the mesh on axis 0.  ``packed`` is ONE
        1-D array of 4-byte words: a device's share is its block of the
        flat ids, then its block of each packed value leaf, so a column
        is a static slice and a ``bitcast_convert_type`` to the leaf's
        dtype (exact: the words are the leaf's own bits).  ``cols`` names
        each value leaf in tree order: the dtype it has in ``packed``, or
        None for a leaf that rides ``beside`` as an array of its own.  The
        ids are the base class's staged ``slot * P + pane`` as they are,
        padding rows (any id at or past ``K * P``) included.  The
        destination shard is derived HERE from the id — key slots are
        owned in contiguous blocks, so it is one division — and padding
        rows are spread over the shards by their row index.  ``cap`` =
        per-(src, dest) bucket capacity (host-known upper bound, so the
        exchange can never overflow; :meth:`_pair_counts` is this rule's
        host twin).  One id column and the value columns ride the
        exchange, one collective each.  Returns the state and a completion
        token (one count cell a device: ready when THIS step has run, the
        gate for reusing the host buffers it read).  The named scopes are
        each stage's name in the program's HLO (every operation's
        ``op_name``), so a device trace's operations can be told apart by
        stage."""
        leaves, counts = leaves_counts
        D = self.n_shards
        K, Pn = layout.K, layout.P
        span = (K // D) * Pn          # flat ids one shard owns
        width = 1 + sum(c is not None for c in cols)

        def step(leaves, counts, packed, *beside):
            from flink_tpu.parallel.exchange import (all_to_all_rows,
                                                     bucket_plan,
                                                     bucket_rows)
            # ---- bucket local rows by destination shard ([D, cap]); the
            # plan keeps each key's records in batch order through the
            # exchange (bit-identical per-cell accumulation at any D)
            with jax.named_scope("exchange_bucket"):
                block = packed.shape[0] // width
                words = (packed[w * block:(w + 1) * block]
                         for w in range(width))
                ids, own = next(words), iter(beside)
                values = [next(own) if c is None else
                          jax.lax.bitcast_convert_type(next(words), c)
                          for c in cols]
                row = jnp.arange(block, dtype=jnp.int32)
                dest = jnp.where(ids < K * Pn, ids // span, row % D)
                flat, _valid = bucket_plan(dest, D, cap)
                bucket = lambda a, fill: bucket_rows(a, flat, D, cap,  # noqa: E731
                                                     fill)
                b_ids = bucket(ids, K * Pn)        # K * Pn = dropped id
                b_vals = [bucket(v, 0) for v in values]
            # ---- the keyed exchange: one collective over ICI per array
            with jax.named_scope("exchange_all_to_all"):
                rx_ids = all_to_all_rows(b_ids).reshape(D * cap)
                rx_vals = tuple(all_to_all_rows(v).reshape((D * cap,)
                                                           + v.shape[2:])
                                for v in b_vals)
            # ---- local scatter-combine: the single-chip fold, in place on
            # this device's ring block; rows that are not ``ok`` carry the
            # dropped id ``span``
            with jax.named_scope("shard_fold"):
                lo = jax.lax.axis_index(KG_AXIS).astype(jnp.int32) * span
                local = rx_ids - lo
                ok = (rx_ids < K * Pn) & (local >= 0) & (local < span)
                lflat = jnp.where(ok, local, span)
                lifted = tuple(jax.tree_util.tree_leaves(
                    self.agg.lift(self._values_tree(rx_vals))))
                new_leaves, new_counts = layout.local.fold(
                    leaves, counts, lflat, lifted, self.kinds,
                    self.agg.combine_leaves)
            with jax.named_scope("completion_token"):
                return new_leaves, new_counts, new_counts[:1]

        rows = P(KG_AXIS)
        state_specs = ((rows,) * len(leaves), rows)
        fn = jax.shard_map(step, mesh=self.mesh,
                           in_specs=state_specs + (rows,) * len(batch),
                           out_specs=state_specs + (rows,), check_vma=False)
        return fn(leaves, counts, *batch)

    def _values_tree(self, flat_values):
        """Rebuild the user value tree from the flat leaves that rode the
        exchange (set by ``_route_batch`` on the host side)."""
        treedef = self._values_treedef
        return jax.tree_util.tree_unflatten(treedef, list(flat_values))

    # ------------------------------------------------------------- host side
    def _pair_counts(self, ids: np.ndarray, live: np.ndarray) -> np.ndarray:
        """Rows each source block sends to each shard, ``[D, D]``: the
        host twin of the destination rule in :meth:`_mesh_update_step`
        (``ids``, ``live`` are ``[D, block]``; a live row goes to the
        shard owning its id, a padding row to ``row index % D``)."""
        D = self.n_shards
        span = (self._K // D) * self._P
        if span & (span - 1):
            dest = ids // span
        else:
            dest = ids >> (span.bit_length() - 1)
        dest = np.where(live, dest,
                        np.arange(ids.shape[1], dtype=np.int32) % D)
        dest += (np.arange(D, dtype=np.int32) * D)[:, None]
        return np.bincount(dest.ravel(), minlength=D * D).reshape(D, D)

    def _packed_acquire(self, shape) -> _PackedBatch:
        """A packed buffer no step still reads (``_staging_acquire``'s
        rule and bound)."""
        pool = self._packed_pool.setdefault(shape, [])
        for pk in pool:
            if pk.ready():
                pk.token = None
                return pk
        pk = _PackedBatch(shape)
        if len(pool) < 4:
            pool.append(pk)
        return pk

    def _route_batch(self, flat_ids, values):
        """The exchange's host routing: PACK the staged flat ids and every
        value leaf that can ride as 4-byte words into one reused host
        buffer ``[D, W, block]`` (W = 1 + packed leaves, block = staged
        length / D rounded up: where the length does not divide by D the
        pack is the padded copy), pick the STICKY bucket capacity, and
        hand the mesh that buffer as ONE 1-D array split on axis 0: four
        transfers a batch whatever the aggregate's column count, where a
        tuple of columns cost one transfer a column and chip.  A leaf
        packs when it has one value a row and its device dtype
        (``canonicalize_dtype``: int64 is int32 with x64 off, the cast
        ``device_put`` would apply) is 4 bytes wide; any other leaf (a
        vector a row, bool, 8- or 16-bit) rides beside the packed array
        as an array of its own, in the same ``device_put``.  Everything
        that is a function of the id — slot, pane, destination shard — is
        derived on the device.  Returns ``(batch, cap, cols, packed)``:
        the first three are the ``_mesh_update_step`` dispatch's
        arguments, the last is the host buffer, to be given the step's
        token.  Timed as phase ``exchange_route`` (inside
        ``device_dispatch``, on the dispatch lane's thread), the pack's
        copy included.
        ``phase_bytes`` counts what the exchange
        then moves (``exchange_sent``, ``exchange_live``: a row is its
        flat id and each value leaf at the width it has ON THE DEVICE;
        ``exchange_value_leaves``: the value leaves handed to the step,
        summed over batches — a leaf ``agg.lift`` never reads is shipped
        to the devices all the same, and dropped from the compiled step
        as dead code) and, beside them, how the routing went:
        ``exchange_packed_leaves`` / ``exchange_unpacked_leaves`` (leaves
        that rode in the packed array / beside it), ``exchange_h2d_arrays``
        (arrays handed to ``device_put``: 1 a batch where every leaf
        packs), ``exchange_route_batches``, of which
        ``exchange_route_copied`` had a staged length that did not divide
        by D and ``exchange_cap_counts_skipped`` took no capacity
        count."""
        with self._phase("exchange_route"):
            D = self.n_shards
            ids = np.asarray(flat_ids)
            vleaves, self._values_treedef = jax.tree_util.tree_flatten(values)
            vleaves = [np.asarray(v) for v in vleaves]
            dtypes = [jax.dtypes.canonicalize_dtype(v.dtype) for v in vleaves]
            cols = tuple(dt if v.ndim == 1 and dt.itemsize == 4 else None
                         for v, dt in zip(vleaves, dtypes))
            B = ids.shape[0]
            block = -(-B // D)
            copied = block * D != B
            full, rest = divmod(B, block)   # whole blocks, rows of the next
            n_packed = len(cols) - cols.count(None)
            pk = self._packed_acquire((D, 1 + n_packed, block))

            def pack(w, a, dtype, fill):
                # device d's rows of column w: a[d * block:(d + 1) * block]
                col = pk.words[:, w].view(dtype)
                col[:full] = a[:full * block].reshape(full, block)
                if copied:
                    col[full, :rest] = a[full * block:]
                    col[full, rest:] = fill
                    col[full + 1:] = fill

            def pad(a):
                out = np.zeros((block * D,) + a.shape[1:], a.dtype)
                out[:B] = a
                return out

            pack(0, ids, np.int32, _PAD_ID)
            beside, word = [], iter(range(1, 1 + n_packed))
            for v, dt in zip(vleaves, cols):
                if dt is None:
                    beside.append(pad(v) if copied else v)
                else:
                    pack(next(word), v, dt, 0)
            ids = pk.words[:, 0]
            live = ids < self._K * self._P
            n_live = int(np.count_nonzero(live))
            # host-known capacity: max rows any (src block, dest) pair
            # sends.  STICKY high-water (the credit-capacity-only-grows
            # rule of ResizingExchange): batch-to-batch skew wobble must
            # not recompile the step — steady state is exactly one compile
            # per (mesh, K, batch geometry), which the tier-1 recompile
            # smoke asserts.  No pair can send more than its block, so at
            # that ceiling nothing is left to count
            skipped = self._exchange_cap_hw >= block
            if not skipped:
                per_pair = self._pair_counts(ids, live)
                self._exchange_cap_hw = max(self._exchange_cap_hw,
                                            _quantize(int(per_pair.max())))
            cap = self._exchange_cap_hw
            batch = jax.device_put((pk.words.reshape(-1), *beside),
                                   self._row_sharding)
            # until the step's own token replaces it: the transfer's end
            pk.token = batch[0]
        # bytes through the all_to_all, padding included (every device
        # sends D buckets of cap rows), and of the rows that carry a
        # record.  A row is counted as it is on the device: its flat id
        # and each leaf at its canonical width (int64 is int32 there with
        # x64 off), packed or not
        row_bytes = 4 + sum(dt.itemsize * math.prod(v.shape[1:])
                            for v, dt in zip(vleaves, dtypes))
        for key, n in (
                ("exchange_sent", D * D * cap * row_bytes),
                ("exchange_live", n_live * row_bytes),
                ("exchange_value_leaves", len(vleaves)),
                ("exchange_packed_leaves", n_packed),
                ("exchange_unpacked_leaves", len(beside)),
                ("exchange_h2d_arrays", len(batch)),
                ("exchange_route_batches", 1),
                ("exchange_route_copied", int(copied)),
                ("exchange_cap_counts_skipped", int(skipped))):
            self.phase_bytes[key] = self.phase_bytes.get(key, 0) + n
        return batch, cap, cols, pk

    def _launch_update(self, flat_ids, values):
        """Intercept the base class's device dispatch (the rest of the host
        front — key probe, lateness, pane bookkeeping, growth, staging —
        is reused verbatim from ``WindowAggOperator``): the staged flat
        ids and value leaves ride the all_to_all data plane to their
        owning shard.  Runs on the dispatch lane's thread: phase
        ``exchange_route``, then the jitted call alone as ``launch``.
        The step's token frees the packed buffer and, returned, the base
        class's staging set."""
        batch, cap, cols, packed = self._route_batch(flat_ids, values)
        with self._phase("launch"):
            self._leaves, self._counts, packed.token = self._mesh_update_step(
                self._layout, (self._leaves, self._counts), batch, cap, cols)
        return self._leaves, self._counts, packed.token

    def _round_key_capacity(self, needed: int) -> int:
        """Key capacity must stay divisible by the shard count (even state
        blocks per device): round the pow2 up to the next multiple of D
        (lcm), which pow2 meshes hit for free.  Paged state never grows —
        K_cap is the pinned resident capacity (overflow pages out)."""
        if self._pager is not None:
            return self._K
        newK = _next_pow2(max(needed, self.n_shards), self._K)
        return newK * self.n_shards // math.gcd(newK, self.n_shards)


class MeshSessionWindowOperator(SessionWindowOperator):
    """Session windows over a device mesh (VERDICT r2 #2).

    Split of responsibilities — the reference's merging-window path
    (``MergingWindowSet.java:62``, ``WindowOperator.java:311-411``) with the
    TPU-first layering of SURVEY §7.3 "Sessions":

    - **Merge decisions stay on the host** (data-dependent control flow —
      interval-set bookkeeping per key, exactly the ``MergingWindowSet``
      role), inherited unchanged from ``SessionWindowOperator``.
    - **The per-batch value FOLD rides the mesh**: the host sessionizes the
      batch (sort + gap breaks — it needs the boundaries for its merge
      anyway), assigns each batch-local session to the shard owning its key
      (``slot % D``), and ships (dest, local session id, values) through one
      ``shard_map`` step: bucket → ``all_to_all`` over ICI → per-shard
      ``segment_sum``/``min``/``max`` — the "device segment merge kernels".
      Only the folded per-session accumulators come back (orders of
      magnitude smaller than the rows).
    - Snapshots stay the base class's raw-key row format — mesh-size
      independent, rescale/split/merge logic reused verbatim.

    Requires declared scatter kinds (add/min/max); generic combines fall
    back to the host fold, which is still shard-partitioned state-wise.
    """

    def __init__(self, *args, mesh: Optional[Mesh] = None,
                 n_devices: Optional[int] = None, **kwargs):
        if mesh is None:
            mesh = make_mesh(n_devices)
        self.mesh = mesh
        self.n_shards = int(mesh.devices.size)
        super().__init__(*args, **kwargs)
        self._row_sharding = NamedSharding(mesh, P(KG_AXIS))
        self._values_treedef = None

    # ------------------------------------------------------------ device op
    @partial(jax.jit, static_argnums=(0, 2, 3))
    def _mesh_fold_step(self, batch, cap: int, cap_sess: int):
        """One sharded fold: per-device bucket rows by destination shard →
        ``all_to_all`` over ICI → per-shard segment combine keyed by the
        (host-assigned) shard-local session id.  ``batch`` = (dest, sid,
        *value_leaves), each row-split over the mesh; returns
        ``[D * cap_sess, *leaf]`` folded accumulators (shard-major)."""
        D = self.n_shards

        def step(dest, sid, *values):
            from flink_tpu.parallel.exchange import (all_to_all_rows,
                                                     bucket_plan,
                                                     bucket_rows)
            flat, _valid = bucket_plan(dest, D, cap)
            bucket = lambda a, fill: bucket_rows(a, flat, D, cap, fill)  # noqa: E731
            rx_sid = all_to_all_rows(bucket(sid, cap_sess)).reshape(D * cap)
            rx_vals = tuple(
                all_to_all_rows(bucket(v, 0)).reshape((D * cap,)
                                                      + v.shape[2:])
                for v in values)
            lifted = tuple(jax.tree_util.tree_leaves(
                self.agg.lift(self._values_tree(rx_vals))))
            outs = []
            for l, kind, init in zip(lifted, self.kinds,
                                     self.spec.leaf_inits):
                acc = jnp.broadcast_to(
                    jnp.asarray(init, l.dtype),
                    (cap_sess,) + l.shape[1:]).copy()
                if kind == "add":
                    outs.append(acc.at[rx_sid].add(l, mode="drop"))
                elif kind == "min":
                    outs.append(acc.at[rx_sid].min(l, mode="drop"))
                else:
                    outs.append(acc.at[rx_sid].max(l, mode="drop"))
            return tuple(outs)

        nv = len(batch) - 2
        in_specs = (P(KG_AXIS), P(KG_AXIS)) + (P(KG_AXIS),) * nv
        out_specs = (P(KG_AXIS),) * self.spec.num_leaves
        fn = jax.shard_map(step, mesh=self.mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        return fn(*batch)

    def _values_tree(self, flat_values):
        return jax.tree_util.tree_unflatten(self._values_treedef,
                                            list(flat_values))

    # ------------------------------------------------------------ host side
    def _sessionize(self, slots, ts, values, bounds=None):
        """Still pads and ``device_put``s column by column, unlike
        ``MeshWindowAggOperator._route_batch``'s one packed array: no
        benchmark cell runs a mesh session job, so a change here could
        not be measured."""
        if self.kinds is None:
            return super()._sessionize(slots, ts, values, bounds)  # host fold
        if self.distinct_column is not None and isinstance(values, dict):
            # the distinct column only feeds the HOST-side value sets
            # (_batch_distinct_sets); never ship it through the exchange
            # (string/object dtypes cannot ride the device anyway)
            values = {k: v for k, v in values.items()
                      if k != self.distinct_column}
        order, s_slots, s_ts, sess_id, firsts, lasts = \
            bounds if bounds is not None else self._session_bounds(slots, ts)
        n_sess = int(firsts.size)
        b_key = s_slots[firsts]
        b_start = s_ts[firsts]
        b_end = s_ts[lasts] + self.gap

        D = self.n_shards
        b_dest = (b_key % D).astype(np.int32)
        # shard-local session numbering (0..n_d-1 per shard)
        counts = np.bincount(b_dest, minlength=D)
        base = np.zeros(D, np.int64)
        base[1:] = np.cumsum(counts)[:-1]
        sess_order = np.argsort(b_dest, kind="stable")
        b_local = np.empty(n_sess, np.int64)
        b_local[sess_order] = np.arange(n_sess) - base[b_dest[sess_order]]
        cap_sess = _quantize(int(counts.max()))

        # per-row routing labels (rows in sorted order)
        row_dest = b_dest[sess_id]
        row_sid = b_local[sess_id].astype(np.int32)
        vleaves, self._values_treedef = jax.tree_util.tree_flatten(values)
        vleaves = [np.asarray(v)[order] for v in vleaves]

        # pad rows to a multiple of D; pad rows carry sid = cap_sess (the
        # segment scatter drops them)
        B = row_dest.size
        Bp = -(-_quantize(-(-B // D) * D, D) // D) * D

        def pad(a, fill, dtype):
            out = np.full((Bp,) + a.shape[1:], fill, dtype)
            out[:B] = a[:B]
            return out

        dest_p = pad(row_dest, 0, np.int32)
        dest_p[B:] = np.arange(Bp - B) % D
        sid_p = pad(row_sid, cap_sess, np.int32)
        src = np.repeat(np.arange(D), Bp // D)
        per_pair = np.bincount(src * D + dest_p, minlength=D * D)
        cap = _quantize(int(per_pair.max()))

        put = lambda a: jax.device_put(a, self._row_sharding)  # noqa: E731
        batch = (put(dest_p), put(sid_p),
                 *(put(pad(v, 0, v.dtype)) for v in vleaves))
        folded = self._mesh_fold_step(batch, cap, cap_sess)
        # gather each session's folded acc from its shard block
        flat_idx = b_dest.astype(np.int64) * cap_sess + b_local
        accs = [np.asarray(l)[flat_idx].astype(dt, copy=False)
                for l, dt in zip(folded, self.spec.leaf_dtypes)]
        return b_key, b_start, b_end, accs
