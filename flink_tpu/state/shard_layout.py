"""Sharding-aware keyed-state layout: one logical state, per-shard slices.

The mesh-sharded ``WindowAggOperator`` (``parallel/mesh_runtime.py``) keeps
its K x P pane cells physically split over a 1-D device mesh (how a device
holds its block is ``ops/pane_layout.ShardRing``'s business):
device ``d`` owns the CONTIGUOUS key-slot block ``[d*K/D, (d+1)*K/D)`` —
the key-group ranges of ``KeyGroupRangeAssignment.java:50-84`` mapped onto
mesh positions (``parallel/mesh.py``).  This module is the snapshot face of
that layout: instead of one dense gid-indexed array per state field, a
mesh snapshot carries **per-shard slices with key-group-range manifests**,
so that

- each shard's slice is produced from (and restores into) exactly the rows
  its device owns — no cross-shard gather is required to WRITE a snapshot,
- a snapshot taken at N shards restores at M shards (either direction,
  M == 1 included) by re-slicing the manifest ranges, the
  ``StateAssignmentOperation.reDistributeKeyedStates`` story, and
- every existing dense-format consumer (cluster rescale via
  ``state/redistribute.py``, savepoint tooling, the single-chip operator)
  keeps working through :func:`densify_keyed_snapshot`, which merges the
  slices back into the dense layout on first touch.

The slices tile ``[0, num_keys)`` in ascending shard order, so merging is a
plain concatenation and splitting is a plain row-slice — the layout never
reorders keys, which is what keeps fire digests and rescale bit-exact.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

#: snapshot keys introduced by the sharded layout
SLICES_KEY = "shard_slices"
LAYOUT_KEY = "shard_layout"

#: state fields sliced along the key-slot axis (leaves is a LIST of arrays,
#: each sliced on axis 0)
_ROW_FIELDS = ("counts", "leaves")


@dataclass(frozen=True)
class ShardLayout:
    """Key-slot ownership of a 1-D mesh: shard ``d`` owns rows
    ``[d * K // D, (d+1) * K // D)`` of the ``[K, ...]`` state arrays
    (``K`` divisible by ``D`` — the operator rounds capacity up)."""

    n_shards: int
    K: int

    def __post_init__(self):
        if self.n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        if self.K % self.n_shards:
            raise ValueError(
                f"key capacity {self.K} not divisible by {self.n_shards} "
                f"shards (round K up first)")

    @property
    def rows_per_shard(self) -> int:
        return self.K // self.n_shards

    def row_range(self, shard: int) -> Tuple[int, int]:
        kd = self.rows_per_shard
        return shard * kd, (shard + 1) * kd

    def shard_of_rows(self, rows: np.ndarray) -> np.ndarray:
        """Owning shard per global row id (clamped: out-of-range sentinel
        rows map onto the last shard, whose scatter drops them anyway)."""
        return np.minimum(np.asarray(rows, np.int64) // self.rows_per_shard,
                          self.n_shards - 1).astype(np.int32)

    def key_group_range(self, shard: int,
                        max_parallelism: int = 128) -> Tuple[int, int]:
        """The contiguous key-group range owned by ``shard`` under the
        reference assignment formula (manifest metadata)."""
        from flink_tpu.core import keygroups
        r = keygroups.key_group_ranges(max_parallelism, self.n_shards)[shard]
        return int(r.start), int(r.end)

    def route_keys(self, keys: np.ndarray,
                   max_parallelism: int = 128) -> np.ndarray:
        """Owning shard per RAW key — the record route (key hash -> murmur
        key group -> contiguous range), the SAME implementation the
        queryable tier's client-side routing uses
        (``core/keygroups.route_raw_keys``): a client that partitions a
        lookup batch with this function lands every key on the server
        that owns its state."""
        from flink_tpu.core.keygroups import route_raw_keys
        return route_raw_keys(keys, self.n_shards, max_parallelism)


def split_to_shard_slices(snap: Dict[str, Any], layout: ShardLayout,
                          max_parallelism: int = 128) -> Dict[str, Any]:
    """Dense gid-indexed snapshot -> per-shard slices + manifest.

    The dense ``counts``/``leaves`` arrays cover rows ``[0, n)`` (live keys
    in global slot order); shard ``d``'s slice is the intersection of its
    row block with ``[0, n)`` — empty blocks (shards past the live keys)
    produce zero-row slices so the manifest always lists every shard."""
    snap = dict(snap)
    counts = snap.pop("counts")
    leaves = snap.pop("leaves")
    n = int(counts.shape[0])
    slices: List[Dict[str, Any]] = []
    for d in range(layout.n_shards):
        lo, hi = layout.row_range(d)
        lo, hi = min(lo, n), min(hi, n)
        slices.append({
            "shard": d,
            "row_range": (int(lo), int(hi)),
            "key_groups": layout.key_group_range(d, max_parallelism),
            "counts": np.asarray(counts[lo:hi]),
            "leaves": [np.asarray(l[lo:hi]) for l in leaves],
        })
    snap[SLICES_KEY] = slices
    snap[LAYOUT_KEY] = {"n_shards": layout.n_shards, "K": layout.K,
                        "max_parallelism": int(max_parallelism),
                        "num_keys": n}
    return snap


def densify_keyed_snapshot(snap: Dict[str, Any]) -> Dict[str, Any]:
    """Merge per-shard slices back into the dense gid-indexed layout.

    No-op (returns ``snap`` unchanged) for snapshots already in the dense
    format, so every restore/rescale path can call it unconditionally.
    Slices may arrive out of order (e.g. after a round trip through a
    coordinator that aggregates per-subtask acks); they are re-tiled by
    their manifest row ranges and must cover ``[0, num_keys)`` exactly."""
    if SLICES_KEY not in snap:
        return snap
    snap = dict(snap)
    slices = snap.pop(SLICES_KEY)
    meta = snap.pop(LAYOUT_KEY, None) or {}
    ordered = sorted(slices, key=lambda s: s["row_range"][0])
    n = int(meta.get("num_keys",
                     max((s["row_range"][1] for s in ordered), default=0)))
    expect = 0
    for s in ordered:
        lo, hi = s["row_range"]
        if lo != expect:
            raise ValueError(
                f"shard slices do not tile [0, {n}): gap/overlap at row "
                f"{expect} (next slice starts at {lo})")
        expect = hi
    if expect != n:
        raise ValueError(f"shard slices cover [0, {expect}) but the "
                         f"manifest says {n} keys")
    live = [s for s in ordered if s["counts"].shape[0]]
    if not live:
        first = ordered[0]
        snap["counts"] = np.asarray(first["counts"])
        snap["leaves"] = [np.asarray(l) for l in first["leaves"]]
        return snap
    snap["counts"] = np.concatenate([s["counts"] for s in live], axis=0)
    snap["leaves"] = [
        np.concatenate([s["leaves"][j] for s in live], axis=0)
        for j in range(len(live[0]["leaves"]))]
    return snap


def has_shard_slices(snap: Dict[str, Any]) -> bool:
    return SLICES_KEY in snap


def slice_manifest(snap: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The manifest rows (shard, row_range, key_groups) without the data —
    observability/REST surface."""
    return [{k: s[k] for k in ("shard", "row_range", "key_groups")}
            for s in snap.get(SLICES_KEY, ())]
