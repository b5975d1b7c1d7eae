"""Device-side record exchange: the data plane, on ICI instead of Netty.

The reference moves records between parallel subtasks through a Netty shuffle
with credit-based flow control (``NettyMessage.java``,
``RemoteInputChannel.java:302``).  On a TPU mesh the equivalent *intra-pod*
exchange is a bucketed ``all_to_all`` under ``shard_map``: each device places
its local records into per-destination buckets of fixed capacity (a row's
cell is its destination's bucket and the count of earlier rows bound the same
way: :func:`bucket_plan`) and one XLA collective rotates the buckets over
ICI.  Capacity overflows are reported by the raw exchange and handled by
:class:`ResizingExchange`, which BLOCKS and re-runs at doubled capacity
instead of dropping — the analog of credit-exhaustion blocking +
floating-buffer redistribution under backlog feedback
(``RemoteInputChannel.java:302``,
``NettyShuffleEnvironmentOptions.java:167``).

All shapes are static (capacity per destination is fixed per compile), so the
exchange jits once; padding rows carry slot id == capacity sentinel and are
dropped by downstream scatters.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from flink_tpu.parallel.mesh import KG_AXIS


def bucket_plan(dest: jnp.ndarray, num_shards: int, cap: int):
    """The shared bucketing plan of every keyed exchange: each local row's
    flat position in the ``[num_shards, cap]`` send buckets, in closed form
    from a D-way count — no sort, no search, no loop.

    Row ``i`` lands in cell ``dest[i] * cap + r``, where ``r`` is the number
    of EARLIER rows of the block with the same destination: a one-hot
    ``[num_shards, B]`` (rows along the minor axis), one cumulative sum
    along it, and the row's own destination picked out.  Returns ``(flat,
    valid_src)``, both in ROW order: ``flat[i]`` the bucket cell of row
    ``i`` (or the ``num_shards * cap`` drop sentinel once its destination's
    bucket is full), ``valid_src`` the in-capacity mask.  The placement is
    the stable sort's, and that matters for more than determinism: records
    of one key keep their batch order through the exchange, which is what
    makes the sharded scatter-combine BIT-identical to the single-chip fold
    (same per-cell accumulation order) at any mesh size.

    The one-hot costs ``num_shards * B`` cells a pass, which at a block of
    16,384 rows and D = 4 is 0.003 ms on a v5e chip.  A stable sort by
    destination with per-destination start offsets costs the same whatever
    D is, but it has every column gathered into sorted order before its
    bucket scatter (0.116 ms a column there): 0.51 ms against 0.16 for the
    plan and two columns.  Only past some hundreds of shards would the
    sort be the cheaper plan (PERF.md section 6, PR 33)."""
    hot = (dest[None, :] == jnp.arange(num_shards, dtype=dest.dtype)[:, None]
           ).astype(jnp.int32)
    earlier = jnp.cumsum(hot, axis=1) - hot
    idx_in_dest = jnp.sum(hot * earlier, axis=0)
    valid_src = idx_in_dest < cap
    flat = jnp.where(valid_src, dest * cap + idx_in_dest, num_shards * cap)
    return flat, valid_src


def bucket_rows(a: jnp.ndarray, flat: jnp.ndarray, num_shards: int, cap: int,
                fill) -> jnp.ndarray:
    """Place one row array into its ``[num_shards, cap, ...]`` send buckets
    under a :func:`bucket_plan`; unfilled cells carry ``fill`` (an id the
    receiving scatter drops, or a neutral value)."""
    buf = jnp.full((num_shards * cap,) + a.shape[1:], fill, a.dtype)
    return buf.at[flat].set(a, mode="drop").reshape(
        (num_shards, cap) + a.shape[1:])


def all_to_all_rows(bucketed: jnp.ndarray) -> jnp.ndarray:
    """The keyed exchange collective: rotate ``[D, cap, ...]`` send buckets
    over the mesh axis so row ``d`` of the result is what device ``d`` sent
    to THIS device — the record→owning-shard route on ICI, replacing the
    host-channel key-shuffle hop (``NettyMessage.java`` analog).  Must run
    inside ``shard_map`` over :data:`~flink_tpu.parallel.mesh.KG_AXIS`."""
    return jax.lax.all_to_all(bucketed, KG_AXIS, split_axis=0,
                              concat_axis=0, tiled=True)


def _bucket_local(dest: jnp.ndarray, leaves: Tuple[jnp.ndarray, ...],
                  num_shards: int, cap: int):
    """Place local rows into [num_shards, cap] buckets by destination shard.

    Returns (bucketed_leaves, valid mask [num_shards, cap], overflow count).
    Rows beyond ``cap`` for a destination overflow (counted, not sent).
    """
    flat, valid_src = bucket_plan(dest, num_shards, cap)
    out_leaves = tuple(bucket_rows(l, flat, num_shards, cap, 0)
                       for l in leaves)
    vmask = jnp.zeros((num_shards * cap,), bool).at[flat].set(
        valid_src, mode="drop").reshape(num_shards, cap)
    overflow = jnp.sum(~valid_src)
    return out_leaves, vmask, overflow


def make_all_to_all_exchange(mesh: Mesh, num_leaves: int, cap: int):
    """Build the jitted exchange: local [B] records -> received [D*cap] rows.

    Inputs (per device, via shard_map):
      dest[B] int32   destination shard per local record
      leaves          tuple of [B, ...] value arrays
    Outputs (per device):
      rx_leaves       tuple of [D*cap, ...] received rows
      rx_valid[D*cap] bool
      overflow        int32 — local rows not sent (capacity exhausted)
    """
    D = mesh.devices.size

    def _exchange(dest, *leaves):
        bucketed, vmask, overflow = _bucket_local(dest, leaves, D, cap)
        # all_to_all over the kg axis: [D, cap, ...] -> [D, cap, ...] where
        # row d of the output came from device d's bucket for *this* device.
        rx = tuple(
            jax.lax.all_to_all(b, KG_AXIS, split_axis=0, concat_axis=0,
                               tiled=True)
            for b in bucketed)
        rx_valid = jax.lax.all_to_all(vmask, KG_AXIS, split_axis=0,
                                      concat_axis=0, tiled=True)
        rx_flat = tuple(r.reshape((D * cap,) + r.shape[2:]) for r in rx)
        return rx_flat, rx_valid.reshape(D * cap), overflow.reshape(1)

    in_specs = (P(KG_AXIS),) + (P(KG_AXIS),) * num_leaves
    out_specs = ((P(KG_AXIS),) * num_leaves, P(KG_AXIS), P(KG_AXIS))
    fn = jax.shard_map(_exchange, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


class ResizingExchange:
    """Zero-loss all_to_all: overflow BLOCKS and renegotiates capacity, it
    never drops (the reference's credit semantics — a sender without credit
    waits, ``RemoteInputChannel.java:302``; floating buffers grow under
    backlog, ``NettyShuffleEnvironmentOptions.java:167``).

    The fixed-cap exchange is pure, so an overflowed round can simply be
    re-run at double capacity with the SAME inputs — one recompile per
    doubling, amortized O(log) over a run.  The overflow check is the one
    host sync per round (the credit check of the hot path); capacity only
    grows, so steady state pays a single scalar readback."""

    def __init__(self, mesh: Mesh, num_leaves: int, cap: int,
                 max_cap: int = 1 << 20):
        self.mesh = mesh
        self.num_leaves = num_leaves
        self.cap = cap
        self.max_cap = max_cap
        self._fn = make_all_to_all_exchange(mesh, num_leaves, cap)

    def __call__(self, dest, *leaves):
        """-> (rx_leaves, rx_valid, cap_used).  Every input row is delivered
        exactly once; raises only if ``max_cap`` cannot hold the skew."""
        while True:
            rx, valid, overflow = self._fn(dest, *leaves)
            if int(jnp.max(overflow)) == 0:
                return rx, valid, self.cap
            if self.cap >= self.max_cap:
                raise RuntimeError(
                    f"exchange overflow at max capacity {self.max_cap}: "
                    f"destination skew exceeds the configured buffer budget")
            self.cap = min(self.cap * 2, self.max_cap)
            self._fn = make_all_to_all_exchange(self.mesh, self.num_leaves,
                                                self.cap)
