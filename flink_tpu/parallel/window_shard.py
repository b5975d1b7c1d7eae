"""Multi-chip windowed aggregation: the sharding-aware operator factory.

Until ISSUE 6 this module built a PLACEMENT-only sharded operator (state
arrays carried a ``NamedSharding`` and XLA's SPMD partitioner split the
kernels, but the probe/mirror host path, paging, snapshots, and the record
route all stayed single-chip).  It now fronts the full mesh runtime
(``parallel/mesh_runtime.MeshWindowAggOperator``): one logical SPMD window
operator whose

- state layout is key-group-range blocks per device
  (``state/shard_layout.ShardLayout``),
- record→owning-shard route is an on-device ``all_to_all`` collective
  (``parallel/exchange``), not a host-channel hop,
- probe/mirror maintenance shards by the same contiguous slot ranges
  (per-shard probes; ``phase_shard_ns`` breakdown),
- snapshots are per-shard slices with key-group-range manifests,
  rescalable across mesh sizes.

This mirrors how the reference scales ``keyBy``: identical operator logic
per subtask, state split by key-group range
(``KeyGroupRangeAssignment.java``), the Netty shuffle replaced by ICI.

``placement_sharded_window_operator`` keeps the old placement-only
construction for A/B comparisons (kernel-partitioning correctness without
the mesh runtime).
"""

from __future__ import annotations

from typing import Optional

from jax.sharding import Mesh

from flink_tpu.operators.window_agg import WindowAggOperator
from flink_tpu.parallel.mesh import make_mesh, state_sharding


def sharded_window_operator(mesh: Optional[Mesh] = None, *,
                            n_devices: Optional[int] = None,
                            **kwargs) -> WindowAggOperator:
    """A window operator whose keyed state, probe path, and record route
    are sharded over ``mesh`` (the full mesh runtime); every
    ``WindowAggOperator`` kwarg passes through unchanged."""
    from flink_tpu.parallel.mesh_runtime import MeshWindowAggOperator
    if mesh is None:
        mesh = make_mesh(n_devices)
    return MeshWindowAggOperator(mesh=mesh, **kwargs)


def placement_sharded_window_operator(mesh: Optional[Mesh] = None, *,
                                      n_devices: Optional[int] = None,
                                      **kwargs) -> WindowAggOperator:
    """The pre-ISSUE-6 construction: single-chip operator logic with state
    arrays placed under a ``NamedSharding`` (XLA splits the kernels; the
    host paths stay unsharded).  Kept for A/B tests."""
    if mesh is None:
        mesh = make_mesh(n_devices)
    return WindowAggOperator(sharding=state_sharding(mesh), **kwargs)
