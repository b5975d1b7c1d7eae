"""Device mesh + key-group sharding: the TPU analog of slot assignment.

The reference assigns contiguous key-group ranges to parallel subtasks
(``KeyGroupRangeAssignment.java:50-84``); here the same ranges map to devices
of a 1-D ``jax.sharding.Mesh`` over axis ``"kg"`` — state arrays are sharded
along their key-slot dimension, and the router (host side or ``all_to_all``
on device) moves each record to the device owning its key group.  Rescaling =
re-slicing ranges over a different mesh, exactly like the reference's
key-group remapping on restore (``StateAssignmentOperation.java``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flink_tpu.core import keygroups

KG_AXIS = "kg"


def make_mesh(n_devices: Optional[int] = None,
              devices: Optional[Sequence] = None) -> Mesh:
    """1-D mesh over the key-group axis (data parallelism over keyed state)."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            if n_devices > len(devices):
                # a silently smaller mesh would finish green with every
                # shard on the first chip
                raise ValueError(
                    f"make_mesh(n_devices={n_devices}): only {len(devices)} "
                    f"{devices[0].platform} device(s) are visible")
            devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (KG_AXIS,))


@dataclass(frozen=True)
class KeyGroupSharding:
    """key group -> mesh-position mapping (contiguous ranges, reference
    formula ``KeyGroupRangeAssignment.computeOperatorIndexForKeyGroup``)."""

    max_parallelism: int
    num_shards: int

    def shard_of_key_group(self, kg: np.ndarray) -> np.ndarray:
        kg = np.asarray(kg, np.int64)
        return (kg * self.num_shards // self.max_parallelism).astype(np.int32)

    def shard_of_keys(self, keys: np.ndarray) -> np.ndarray:
        kg = keygroups.assign_to_key_group(keygroups.hash_keys(keys),
                                           self.max_parallelism)
        return self.shard_of_key_group(kg)

    def ranges(self) -> List["keygroups.KeyGroupRange"]:
        return keygroups.key_group_ranges(self.max_parallelism, self.num_shards)


def state_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for [K_total, ...] state: key-slot dim split over the mesh."""
    return NamedSharding(mesh, P(KG_AXIS))


def layout_for(mesh: Mesh, K: int):
    """The key-group-range state layout of a [K, ...] array over ``mesh``
    (``state/shard_layout.ShardLayout``): device ``d`` owns the contiguous
    slot block ``[d*K/D, (d+1)*K/D)`` — the rows ``state_sharding`` places
    on it.  The single source of row-ownership truth shared by snapshots
    (per-shard slices + manifests), the sharded probe (contiguous-range
    shard ownership), and the record router (dest = slot // (K/D))."""
    from flink_tpu.state.shard_layout import ShardLayout
    return ShardLayout(int(mesh.devices.size), K)
