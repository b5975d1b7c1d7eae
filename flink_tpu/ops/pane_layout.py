"""Physical layouts of the keyed pane state: K key rows x P ring slots of
accumulator cells, one device array per accumulator leaf plus the int32
element counts.

The window operator addresses a cell as ``(row, slot)`` and the host ships
one id per record, ``row * P + slot`` (the native probe pass writes the same
ids); everything that knows how those cells sit in memory lives here, behind
three classes with one interface:

- :class:`PaneRing` — ``[P * K, *leaf]``, pane-major: cell ``(row, slot)``
  sits at ``slot * K + row``.  The per-batch fold is then a 1-D scatter into
  the array as it is held between steps, in place on the donated buffer; a
  pane column is a contiguous slice, so fires, snapshots and clears read and
  write slices at memory speed.  (A ``[K, P]`` array is kept key-minor by
  the TPU runtime and its scatter runs on a flat row-major copy: four
  whole-state passes per leaf and step, PERF.md section 6, PR 27.)
  ``WindowAggOperator`` with unsharded state resolves to it.
- :class:`ShardRing` — ``[D * P * Ks, *leaf]``, ``Ks = K / D``: one
  ``PaneRing(Ks, P)`` per device of a key-group mesh, the D blocks end to
  end on axis 0, which shards over the mesh.  Device ``d`` holds the key
  rows ``[d * Ks, (d + 1) * Ks)`` it owns by key group, pane-major, so the
  sharded step folds with the same in-place 1-D scatter and a pane column
  is a contiguous slice on every device.  ``MeshWindowAggOperator``
  (``parallel/mesh_runtime.py``) resolves to it.
- :class:`KeyGrid` — ``[K, P, *leaf]``, key-major: the key axis leads, so
  the array shards by key group under plain GSPMD placement
  (``parallel/window_shard.py``'s A/B operator, the one resolver left) and
  a device's block is its contiguous key-row range.

A layout is a hashable value (its geometry): the jitted steps take it as a
static argument, since ``P`` is not in a ring array's shape.  Methods take
and return single arrays, so state leaves, the counts and the device-probe
delta twins all go through the same code.  Index conventions: ``slots`` and
``rows`` are int32 vectors whose length is static; a slot ``>= P`` or a row
``>= K`` is padding, never written, and reads of it give ``fill``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from flink_tpu.ops.scatter import (combine_along_axis, scatter_fold_counts,
                                   scatter_generic)

#: the scatter id no capacity holds: padding rows and probe misses carry it
#: and every layout's fold drops it (``mode="drop"``)
DROP_ID = np.int32(np.iinfo(np.int32).max)


def _bcast(mask, like):
    """Reshape a leading-axes mask to broadcast against ``like``."""
    return mask.reshape(mask.shape + (1,) * (like.ndim - mask.ndim))


def _fold_flat(flat_leaves, flat_counts, ids, lifted, kinds, combine_leaves):
    """The fold's body on flat state: scatter-combine by kind with the
    exact counts beside it, or the sort + segmented-scan fold of an
    arbitrary monoid (``kinds is None``)."""
    if kinds is not None:
        return scatter_fold_counts(flat_leaves, flat_counts, ids, lifted,
                                   kinds)
    with jax.named_scope("leaf_scatter_generic"):
        new_leaves = scatter_generic(flat_leaves, ids, lifted, combine_leaves,
                                     flat_counts.shape[0])
    with jax.named_scope("count_fold"):
        ones = jnp.ones(ids.shape, jnp.int32)
        return new_leaves, flat_counts.at[ids].add(ones, mode="drop")


@dataclass(frozen=True)
class PaneRing:
    """Pane-major ``[P * K, *leaf]``; ``P`` a power of two."""

    K: int
    P: int

    def __post_init__(self):
        if self.P <= 0 or self.P & (self.P - 1):
            raise ValueError(f"pane ring size {self.P} is no power of two")

    # ------------------------------------------------------------ geometry
    def shape(self, leaf_shape=()):
        return (self.P * self.K,) + tuple(leaf_shape)

    def full(self, init, leaf_shape, dtype):
        return jnp.broadcast_to(jnp.asarray(init, dtype),
                                self.shape(leaf_shape)).copy()

    def cell_ids(self, flat_ids):
        """Host ids ``row * P + slot`` -> ring ids ``slot * K + row``;
        anything at or past row ``K`` (``_PAD_ID``, probe misses) drops."""
        row = flat_ids >> (self.P.bit_length() - 1)
        slot = flat_ids & (self.P - 1)
        return jnp.where(row < self.K, slot * self.K + row, DROP_ID)

    def _starts(self, slots):
        """Per slot: its column's first index (a pad reads the last
        column) and whether it is a real slot."""
        return [(jnp.minimum(slots[j], self.P - 1) * self.K,
                 slots[j] < self.P) for j in range(slots.shape[0])]

    # ---------------------------------------------------------------- fold
    def fold(self, leaves, counts, flat_ids, lifted, kinds: Sequence[str],
             combine_leaves: Callable = None):
        """One batch into the state: the arrays are their own flat view."""
        with jax.named_scope("ring_ids"):
            ids = self.cell_ids(flat_ids)
        return _fold_flat(leaves, counts, ids, lifted, kinds, combine_leaves)

    # ------------------------------------------------------------- columns
    def columns(self, a, slots, rows=None, fill=None):
        """``[rows, len(slots), *leaf]``: the first ``rows`` key rows
        (default all) of the pane columns ``slots``."""
        n = self.K if rows is None else rows
        cols = []
        for start, real in self._starts(slots):
            col = jax.lax.dynamic_slice_in_dim(a, start, n, axis=0)
            if fill is not None:
                col = jnp.where(real, col, jnp.asarray(fill, a.dtype))
            cols.append(col)
        return jnp.stack(cols, axis=1)

    def set_columns(self, a, slots, cols):
        """Write ``cols [n, len(slots), *leaf]`` (``n <= K``) over the
        first ``n`` rows of the pane columns ``slots``."""
        n = cols.shape[0]
        for j, (start, real) in enumerate(self._starts(slots)):
            cur = jax.lax.dynamic_slice_in_dim(a, start, n, axis=0)
            new = jnp.where(real, cols[:, j].astype(a.dtype), cur)
            a = jax.lax.dynamic_update_slice_in_dim(a, new, start, axis=0)
        return a

    def fill_columns(self, a, slots, init):
        fill = jnp.broadcast_to(jnp.asarray(init, a.dtype),
                                (self.K, slots.shape[0]) + a.shape[1:])
        return self.set_columns(a, slots, fill)

    # --------------------------------------------------------------- cells
    def _cell_index(self, rows, slots):
        ok = (rows < self.K)[:, None] & (slots < self.P)[None, :]
        return jnp.where(ok, slots[None, :] * self.K + rows[:, None], DROP_ID)

    def cells(self, a, rows, slots):
        """The ``rows x slots`` sub-grid, ``[V, m, *leaf]`` (pads clip)."""
        return jnp.take(a, self._cell_index(rows, slots), axis=0,
                        mode="clip")

    def set_cells(self, a, rows, slots, vals):
        return a.at[self._cell_index(rows, slots)].set(
            vals.astype(a.dtype), mode="drop")

    def fill_rows(self, a, rows, init):
        """Reset whole key rows (every slot) to ``init``."""
        every = jnp.arange(self.P, dtype=jnp.int32)
        fill = jnp.broadcast_to(jnp.asarray(init, a.dtype),
                                (rows.shape[0], self.P) + a.shape[1:])
        return self.set_cells(a, rows, every, fill)

    def where_rows(self, a, key_mask, init):
        """``init`` in every cell of the rows ``key_mask [K]`` marks."""
        mask = _bcast(jnp.tile(key_mask, self.P), a)
        return jnp.where(mask, jnp.asarray(init, a.dtype), a)

    def combine_panes_at(self, leaves, slots, idx, combine_leaves: Callable):
        """Per leaf ``[len(idx), *leaf]``: the combine over the panes
        ``slots`` of the key rows ``idx``.  The columns are contiguous, so
        all K rows combine at memory speed and ``idx`` gathers from the
        one combined column (an element gather costs the chip some 7 ns
        a cell, so it comes last, on 1/m of the cells)."""
        with jax.named_scope("pane_columns"):
            sel = tuple(self.columns(l, slots) for l in leaves)
        with jax.named_scope("pane_combine"):
            combined = combine_along_axis(sel, combine_leaves, axis=1)
        with jax.named_scope("emit_rows_gather"):
            return tuple(jnp.take(c, idx, axis=0, mode="clip")
                         for c in combined)

    # -------------------------------------------------------------- growth
    def _view(self, a):
        return a.reshape((self.P, self.K) + a.shape[1:])

    def grow_keys(self, a, new_k: int, init):
        """The same cells in a ring of ``new_k >= K`` rows."""
        pad = jnp.broadcast_to(jnp.asarray(init, a.dtype),
                               (self.P, new_k - self.K) + a.shape[1:])
        grown = jnp.concatenate([self._view(a), pad], axis=1)
        return grown.reshape((self.P * new_k,) + a.shape[1:])

    def grow_panes(self, a, new_p: int, init, src_slots, dst_slots):
        """A ring of ``new_p`` slots holding the columns ``src_slots`` of
        this one at ``dst_slots``, ``init`` elsewhere."""
        new = PaneRing(self.K, new_p)
        fresh = new._view(new.full(init, a.shape[1:], a.dtype))
        fresh = fresh.at[dst_slots].set(self._view(a)[src_slots])
        return fresh.reshape(new.shape(a.shape[1:]))


@dataclass(frozen=True)
class ShardRing:
    """One ``PaneRing(K / D, P)`` per device of a 1-D key-group mesh:
    ``[D * P * Ks, *leaf]``, axis 0 sharded over the mesh's axis, cell
    ``(row, slot)`` at ``(row // Ks) * P * Ks + slot * Ks + row % Ks``.

    The fold and the columnar methods run :class:`PaneRing`'s own method
    on each device's block under ``shard_map`` (key axis of inputs and
    outputs on the mesh axis, so ``columns`` gives ``[K, m]`` in global key
    order); the row-addressed methods translate a global row to the flat
    index and leave the placement to the compiler: they serve set-up and
    the paged, host and degraded tiers, never a steady step."""

    K: int
    P: int
    mesh: Mesh

    def __post_init__(self):
        if self.K % self.D:
            raise ValueError(f"{self.K} key rows do not split over "
                             f"{self.D} devices")

    # ------------------------------------------------------------ geometry
    @property
    def D(self) -> int:
        return int(self.mesh.devices.size)

    @property
    def local(self) -> PaneRing:
        """A device's block: the layout the sharded step folds into."""
        return PaneRing(self.K // self.D, self.P)

    @property
    def _axis(self) -> str:
        return self.mesh.axis_names[0]

    @property
    def _rows(self) -> PartitionSpec:
        return PartitionSpec(self._axis)

    def _kept(self, a):
        """``a`` held to the state's sharding: a step that follows finds
        its blocks where it left them."""
        return jax.lax.with_sharding_constraint(
            a, NamedSharding(self.mesh, self._rows))

    def _per_shard(self, fn, *sharded, shared=()):
        """``fn(*blocks, *shared)`` on every device: the arrays of
        ``sharded`` split on axis 0, those of ``shared`` whole, every
        array of the result split again."""
        step = jax.shard_map(
            fn, mesh=self.mesh,
            in_specs=(self._rows,) * len(sharded)
            + (PartitionSpec(),) * len(shared),
            out_specs=self._rows, check_vma=False)
        return step(*sharded, *shared)

    def shape(self, leaf_shape=()):
        return (self.P * self.K,) + tuple(leaf_shape)

    def full(self, init, leaf_shape, dtype):
        return jnp.broadcast_to(jnp.asarray(init, dtype),
                                self.shape(leaf_shape)).copy()

    # ---------------------------------------------------------------- fold
    def fold(self, leaves, counts, flat_ids, lifted, kinds: Sequence[str],
             combine_leaves: Callable = None):
        """One batch of GLOBAL ids into the state: every device sees the
        whole batch and folds the ids of its own key rows.  (The mesh
        operator's step exchanges the records first and folds through
        :attr:`local` itself.)"""
        local = self.local
        span = local.K * self.P
        axis = self._axis

        def fold(leaves, counts, ids, lifted):
            mine = ids - jax.lax.axis_index(axis).astype(jnp.int32) * span
            mine = jnp.where((mine >= 0) & (mine < span), mine, span)
            return local.fold(leaves, counts, mine, lifted, kinds,
                              combine_leaves)

        return self._per_shard(fold, tuple(leaves), counts,
                               shared=(flat_ids, tuple(lifted)))

    # ------------------------------------------------------------- columns
    def columns(self, a, slots, rows=None, fill=None):
        local = self.local
        cols = self._per_shard(
            lambda block, slots: local.columns(block, slots, fill=fill),
            a, shared=(slots,))
        if rows is not None and rows < self.K:
            cols = jax.lax.slice_in_dim(cols, 0, rows, axis=0)
        return cols

    def set_columns(self, a, slots, cols):
        local = self.local
        cols = cols.astype(a.dtype)
        if cols.shape[0] < self.K:
            # key rows past the last one given keep what they hold
            cols = jnp.concatenate(
                [cols, self.columns(a, slots)[cols.shape[0]:]])
        return self._per_shard(
            lambda block, cols, slots: local.set_columns(block, slots, cols),
            a, cols, shared=(slots,))

    def fill_columns(self, a, slots, init):
        local = self.local
        return self._per_shard(
            lambda block, slots: local.fill_columns(block, slots, init),
            a, shared=(slots,))

    def where_rows(self, a, key_mask, init):
        local = self.local
        return self._per_shard(
            lambda block, mask: local.where_rows(block, mask, init),
            a, key_mask)

    # --------------------------------------------------------------- cells
    def _cell_index(self, rows, slots):
        ks = self.local.K
        ok = (rows < self.K)[:, None] & (slots < self.P)[None, :]
        at = ((rows // ks) * (self.P * ks) + rows % ks)[:, None] \
            + slots[None, :] * ks
        return jnp.where(ok, at, DROP_ID)

    def cells(self, a, rows, slots):
        return jnp.take(a, self._cell_index(rows, slots), axis=0,
                        mode="clip")

    def set_cells(self, a, rows, slots, vals):
        return self._kept(a.at[self._cell_index(rows, slots)].set(
            vals.astype(a.dtype), mode="drop"))

    def fill_rows(self, a, rows, init):
        every = jnp.arange(self.P, dtype=jnp.int32)
        fill = jnp.broadcast_to(jnp.asarray(init, a.dtype),
                                (rows.shape[0], self.P) + a.shape[1:])
        return self.set_cells(a, rows, every, fill)

    def combine_panes_at(self, leaves, slots, idx, combine_leaves: Callable):
        """Gather the ``idx`` rows first, as :class:`KeyGrid` does: they
        may sit on any device."""
        with jax.named_scope("emit_rows_pane_gather"):
            sel = tuple(self.cells(l, idx, slots) for l in leaves)
        with jax.named_scope("pane_combine"):
            return combine_along_axis(sel, combine_leaves, axis=1)

    # -------------------------------------------------------------- growth
    def grow_keys(self, a, new_k: int, init):
        """The same cells in ``new_k >= K`` rows.  A device's key range
        moves with K, so rows change devices: through the logical
        ``[K, P]`` view and back (set-up only)."""
        ks, leaf = self.local.K, a.shape[1:]
        grid = jnp.moveaxis(a.reshape((self.D, self.P, ks) + leaf), 1, 2)
        grown = KeyGrid(self.K, self.P).grow_keys(
            grid.reshape((self.K, self.P) + leaf), new_k, init)
        blocks = jnp.moveaxis(
            grown.reshape((self.D, new_k // self.D, self.P) + leaf), 2, 1)
        return self._kept(blocks.reshape((self.P * new_k,) + leaf))

    def grow_panes(self, a, new_p: int, init, src_slots, dst_slots):
        local = self.local
        return self._per_shard(
            lambda block, init, src, dst: local.grow_panes(
                block, new_p, init, src, dst),
            a, shared=(jnp.asarray(init, a.dtype), src_slots, dst_slots))


@dataclass(frozen=True)
class KeyGrid:
    """Key-major ``[K, P, *leaf]``: axis 0 shards by key group."""

    K: int
    P: int

    def shape(self, leaf_shape=()):
        return (self.K, self.P) + tuple(leaf_shape)

    def full(self, init, leaf_shape, dtype):
        return jnp.broadcast_to(jnp.asarray(init, dtype),
                                self.shape(leaf_shape)).copy()

    def fold(self, leaves, counts, flat_ids, lifted, kinds: Sequence[str],
             combine_leaves: Callable = None):
        """One batch into the state, through a flat row-major view: a
        relayout on a chip, which keeps the grid key-minor (0.81-0.86 ms
        an array of ``[262144, P]`` and step on a v5e, more than the
        scatter itself: PERF.md section 6, PRs 33, 35 and 36; the mesh
        operator left this class for :class:`ShardRing` over it)."""
        n = self.K * self.P
        with jax.named_scope("state_flatten"):
            flat = tuple(l.reshape((n,) + l.shape[2:]) for l in leaves)
            flat_counts = counts.reshape(n)
        new, new_counts = _fold_flat(flat, flat_counts, flat_ids, lifted,
                                     kinds, combine_leaves)
        with jax.named_scope("state_unflatten"):
            return (tuple(l.reshape(self.shape(l.shape[1:])) for l in new),
                    new_counts.reshape(self.K, self.P))

    def columns(self, a, slots, rows=None, fill=None):
        if rows is not None and rows < a.shape[0]:
            a = jax.lax.slice_in_dim(a, 0, rows, axis=0)
        if fill is None:
            return jnp.take(a, slots, axis=1)
        return jnp.take(a, slots, axis=1, mode="fill", fill_value=fill)

    def set_columns(self, a, slots, cols):
        return a.at[:cols.shape[0], slots].set(cols.astype(a.dtype),
                                               mode="drop")

    def fill_columns(self, a, slots, init):
        fill = jnp.broadcast_to(jnp.asarray(init, a.dtype),
                                (self.K, slots.shape[0]) + a.shape[2:])
        return a.at[:, slots].set(fill, mode="drop")

    def cells(self, a, rows, slots):
        return jnp.take(jnp.take(a, rows, axis=0, mode="clip"), slots,
                        axis=1, mode="clip")

    def set_cells(self, a, rows, slots, vals):
        return a.at[rows[:, None], slots[None, :]].set(
            vals.astype(a.dtype), mode="drop")

    def fill_rows(self, a, rows, init):
        fill = jnp.broadcast_to(jnp.asarray(init, a.dtype),
                                (rows.shape[0],) + a.shape[1:])
        return a.at[rows].set(fill, mode="drop")

    def where_rows(self, a, key_mask, init):
        return jnp.where(_bcast(key_mask, a), jnp.asarray(init, a.dtype), a)

    def combine_panes_at(self, leaves, slots, idx, combine_leaves: Callable):
        """Gather the ``idx`` rows first: compute scales with rows
        emitted, not key capacity."""
        with jax.named_scope("emit_rows_pane_gather"):
            sel = tuple(self.cells(l, idx, slots) for l in leaves)
        with jax.named_scope("pane_combine"):
            return combine_along_axis(sel, combine_leaves, axis=1)

    def grow_keys(self, a, new_k: int, init):
        fresh = KeyGrid(new_k, self.P).full(init, a.shape[2:], a.dtype)
        return fresh.at[:self.K].set(a)

    def grow_panes(self, a, new_p: int, init, src_slots, dst_slots):
        fresh = KeyGrid(self.K, new_p).full(init, a.shape[2:], a.dtype)
        return fresh.at[:, dst_slots].set(jnp.take(a, src_slots, axis=1))
