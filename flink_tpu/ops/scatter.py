"""Scatter-combine kernels: fold a record batch into dense keyed device state.

This replaces the reference's per-record state-map probe+update
(``CopyOnWriteStateMap.transform`` called from ``HeapAggregatingState.java:42``
for every element, SURVEY §3.3 hot loop (c)) with ONE fused device op per
micro-batch over ``[num_slots, ...]`` dense state:

- **fast path** — when every accumulator leaf's ``combine`` is an elementwise
  add/min/max (covers sum/count/avg/min/max and products thereof, i.e. every
  built-in reference aggregation, ``SumAggregator.java``/``ComparableAggregator.java``),
  the whole batch folds with ``state.at[idx].add|min|max(lifted)`` — a single
  XLA scatter per leaf that TPU executes without host round-trips.

- **generic path** — any associative+commutative ``combine`` (the reference's
  ``AggregateFunction.merge`` contract, ``AggregateFunction.java:114``): sort
  the batch by slot id, run a *segmented* ``lax.associative_scan`` (flag/value
  pairs), and scatter each segment's total with ``.at[].set`` — indices are
  unique after segmentation, so arbitrary monoids stay race-free.

Out-of-range slot ids (>= num_slots) are dropped by XLA scatter semantics —
padding rows use that to make batch shapes static (no recompiles per batch).

Every function here folds into FLAT state: one leading axis of cells.  How
the window operator's K x P pane cells map onto that axis — the pane-major
ring it holds on one chip and, a block a device, on a mesh, whose arrays
are their own flat view, or the key-major ``[K, P]`` grid that GSPMD
placement shards — is ``ops/pane_layout.py``'s
business; its ``fold`` hands ``scatter_fold_counts`` the flat arrays and the
cell ids in that layout.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

#: scatter kinds an accumulator leaf may declare
SCATTER_KINDS = ("add", "min", "max")


def _bcast_flags(flags, like):
    """Reshape [B] flags to broadcast against a [B, ...] leaf."""
    extra = like.ndim - 1
    return flags.reshape(flags.shape + (1,) * extra)


def scatter_fast(state_leaves, slot_ids, lifted_leaves, kinds: Sequence[str]):
    """Fold lifted [B, ...] leaves into [N, ...] state via add/min/max scatters.

    slot_ids: int32[B]; ids == N (out of range) are dropped (padding).
    """
    out = []
    for leaf, lifted, kind in zip(state_leaves, lifted_leaves, kinds):
        ref = leaf.at[slot_ids]
        if kind == "add":
            out.append(ref.add(lifted.astype(leaf.dtype), mode="drop"))
        elif kind == "min":
            out.append(ref.min(lifted.astype(leaf.dtype), mode="drop"))
        elif kind == "max":
            out.append(ref.max(lifted.astype(leaf.dtype), mode="drop"))
        else:
            raise ValueError(f"unknown scatter kind {kind!r}")
    return tuple(out)


def scatter_fold_counts(flat_leaves, flat_counts, slot_ids, lifted_leaves,
                        kinds: Sequence[str]):
    """One batch's fold into FLAT keyed state: the value leaves
    scatter-combine by kind and the element counts scatter-add ones.  The
    body of every lane's fold — ``pane_layout``'s ``fold`` calls it for the
    per-batch ``_update_step``, the device-probe delta fold and the fused
    scan megastep's per-step fold (window_agg), so the lanes cannot drift
    arithmetically.  Out-of-range ids (padding, probe misses) drop.  Each
    scatter carries a named scope: its name in a device trace."""
    new_leaves = ()
    for i, kind in enumerate(kinds):
        with jax.named_scope(f"leaf{i}_scatter_{kind}"):
            new_leaves += scatter_fast(flat_leaves[i:i + 1], slot_ids,
                                       lifted_leaves[i:i + 1], (kind,))
    with jax.named_scope("count_fold"):
        # ones made device-side: the upload stays ids + values only
        ones = jnp.ones(slot_ids.shape, jnp.int32)
        return new_leaves, flat_counts.at[slot_ids].add(ones, mode="drop")


def segment_fold(slot_ids, lifted_leaves, combine_leaves: Callable,
                 num_slots: int = 0):
    """Generic per-batch segment reduction: returns (unique_slot_ids[B],
    is_segment_end[B], folded_leaves[B, ...]) where rows flagged as segment
    ends hold the full fold of their slot's records in this batch.

    combine_leaves(a_leaves, b_leaves) -> leaves; must be associative +
    commutative per the ``AggregateFunction.merge`` contract.
    """
    _, sids, is_end, folded = segment_running_fold(slot_ids, lifted_leaves,
                                                   combine_leaves)
    return sids, is_end, folded


def segment_running_fold(slot_ids, lifted_leaves, combine_leaves: Callable):
    """Per-record *running* segment fold (keyed ``reduce()`` semantics:
    every input record emits its key's fold-so-far within the batch).

    Returns (order[B], sids[B], is_end[B], prefix_leaves[B, ...]) where
    ``prefix_leaves[i]`` is the inclusive fold of sorted rows of the same slot
    up to i; ``order`` maps sorted position -> original row.
    """
    order = jnp.argsort(slot_ids)
    sids = slot_ids[order]
    svals = tuple(l[order] for l in lifted_leaves)
    first = jnp.concatenate([jnp.ones((1,), bool), sids[1:] != sids[:-1]])

    def seg_op(a, b):
        fa, va = a[0], a[1:]
        fb, vb = b[0], b[1:]
        merged = combine_leaves(va, vb)
        vals = tuple(
            jnp.where(_bcast_flags(fb, m), y, m)
            for m, y in zip(merged, vb)
        )
        return (fa | fb,) + vals

    scanned = jax.lax.associative_scan(seg_op, (first,) + svals)
    is_end = jnp.concatenate([sids[1:] != sids[:-1], jnp.ones((1,), bool)])
    return order, sids, is_end, scanned[1:]


def scatter_generic(state_leaves, slot_ids, lifted_leaves,
                    combine_leaves: Callable, num_slots: int):
    """Fold a batch into state with an arbitrary monoid combine.

    1. segment-fold the batch per slot (associative scan),
    2. gather current state at each segment-end slot,
    3. combine and ``.at[].set`` — segment-end slots are unique, so the
       read-modify-write races the reference solves with single-threaded
       mailboxing (``MailboxProcessor.java:66``) cannot occur.
    """
    sids, is_end, folded = segment_fold(slot_ids, lifted_leaves, combine_leaves, num_slots)
    write_ids = jnp.where(is_end, sids, num_slots)  # non-ends dropped
    safe_gather = jnp.minimum(sids, num_slots - 1)
    current = tuple(l[safe_gather] for l in state_leaves)
    merged = combine_leaves(current, folded)
    return tuple(
        l.at[write_ids].set(m.astype(l.dtype), mode="drop")
        for l, m in zip(state_leaves, merged)
    )


def combine_along_axis(leaves, combine_leaves: Callable, axis: int, keepdims: bool = False):
    """Tree-reduce leaves along ``axis`` with an arbitrary monoid — the fire-time
    pane combine (blockwise partials → window total, SURVEY §5.7). Log-depth."""
    n = leaves[0].shape[axis]

    def take(ls, sl):
        return tuple(jax.lax.slice_in_dim(l, sl.start, sl.stop, axis=axis) for l in ls)

    cur = leaves
    size = n
    while size > 1:
        half = size // 2
        a = take(cur, slice(0, half))
        b = take(cur, slice(half, 2 * half))
        merged = combine_leaves(a, b)
        if size % 2:
            tail = take(cur, slice(2 * half, size))
            merged = tuple(jnp.concatenate([m, t], axis=axis) for m, t in zip(merged, tail))
            size = half + 1
        else:
            size = half
        cur = merged
    if keepdims:
        return cur
    return tuple(jnp.squeeze(l, axis=axis) for l in cur)
