"""Ring combine: blockwise partial aggregation rotated over ICI.

The ring-attention analog for streaming state (SURVEY §5.7): when one
logical window's panes span multiple chips (sequence/context parallelism —
the pane axis sharded instead of the key axis), the window total is the
monoid combine of per-chip partials.  Instead of an all-gather (O(D) memory
on every chip), partials rotate around the ring with ``lax.ppermute`` —
each step combines the neighbor's partial into the running accumulator, and
after D-1 rotations every chip holds the full combine.  Bandwidth per step
is one partial, exactly the blockwise-attention communication pattern.

Also provided: ``ring_all_reduce_sum`` (the reduce-scatter + all-gather
decomposition) for plain additive state.
"""

from __future__ import annotations

from typing import Callable

import jax
from jax.sharding import Mesh, PartitionSpec as P

from flink_tpu.parallel.mesh import KG_AXIS


def _ring_fold(leaves, combine_leaves: Callable, axis: str, D: int):
    """D-1 ppermute rotations folding every device's partial into all
    devices.  Arrival order is a per-device cyclic rotation, so
    ``combine_leaves`` must be associative AND COMMUTATIVE — the
    ``AggregateFunction.combine`` contract (core/functions.py); an
    order-sensitive combine would yield device-dependent results."""
    perm = [(i, (i + 1) % D) for i in range(D)]
    acc = leaves
    rotating = leaves
    for _ in range(D - 1):
        rotating = tuple(jax.lax.ppermute(l, axis, perm) for l in rotating)
        acc = combine_leaves(acc, rotating)
    return acc


def make_ring_combine(mesh: Mesh, combine_leaves: Callable,
                      num_leaves: int, axis: str = KG_AXIS):
    """Build a jitted ring combine over ``axis``.

    Input: per-device partial accumulator leaves (each [*leaf_shape], one
    partial per chip, sharded over ``axis`` with a leading device dim).
    Output: the SAME shape, every device holding the full combine of all
    partials.  ``combine_leaves`` must be associative AND commutative
    (the ``AggregateFunction.combine`` contract) — partials arrive in a
    per-device cyclic order.
    """
    D = mesh.shape[axis]

    def ring(*leaves):
        # leaves: per-device local partial (shard_map strips the device dim)
        return _ring_fold(leaves, combine_leaves, axis, D)

    specs = tuple(P(axis) for _ in range(num_leaves))
    fn = jax.shard_map(ring, mesh=mesh, in_specs=specs, out_specs=specs,
                       check_vma=False)
    return jax.jit(fn)


def make_ring_all_reduce_sum(mesh: Mesh, axis: str = KG_AXIS):
    """Additive special case: psum over the ring axis (XLA lowers this to
    the bidirectional ring reduce on ICI)."""

    def allreduce(x):
        return jax.lax.psum(x, axis)

    fn = jax.shard_map(allreduce, mesh=mesh, in_specs=P(axis),
                       out_specs=P(axis), check_vma=False)
    return jax.jit(fn)


def sharded_pane_window_total(mesh: Mesh, combine_leaves: Callable,
                              num_leaves: int, axis: str = KG_AXIS):
    """Sequence-parallel window fire: each chip holds a PANE SLICE of the
    window's accumulator state ``[K, panes_local, ...]``; the full window
    total per key = ring-combine of the per-chip pane combines.

    Returns a jitted fn(leaves...) -> combined leaves [K, ...] replicated
    across the ring (every chip can emit its key shard of the result).
    """
    from flink_tpu.ops.scatter import combine_along_axis

    D = mesh.shape[axis]

    def body(*leaves):
        # per-device view [1, K, panes_local, ...]: combine the LOCAL pane
        # slice first (blockwise partial) so the ring carries [1, K, ...],
        # not the full pane axis
        local = combine_along_axis(leaves, combine_leaves, axis=2)
        return _ring_fold(local, combine_leaves, axis, D)

    specs = tuple(P(axis) for _ in range(num_leaves))
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=specs,
                                 out_specs=specs, check_vma=False))
