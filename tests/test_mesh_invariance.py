"""Shard-count invariance of the mesh-sharded hot path (ISSUE 6).

One logical window operator across the chip mesh: fire digests and operator
counters must be BIT-identical at mesh sizes 1 vs 2 vs 4 on every tier
(host mirror / device / deferred), with cold-key paging riding per-shard,
snapshots rescaling across mesh sizes in both directions, and the pjit'd
update step compiling exactly once per (mesh size, batch geometry) — a
resharding-induced recompile fails the smoke.  Runs on the 8-device
virtual CPU mesh the conftest forces (``JAX_PLATFORMS=cpu`` +
``--xla_force_host_platform_device_count=8``), so tier-1 exercises real
multi-device sharding.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flink_tpu.core.batch import RecordBatch, Watermark
from flink_tpu.core.functions import RuntimeContext, SumAggregator
from flink_tpu.operators.window_agg import WindowAggOperator
from flink_tpu.parallel.mesh import make_mesh
from flink_tpu.parallel.mesh_runtime import MeshWindowAggOperator
from flink_tpu.state.paging import PagingConfig
from flink_tpu.state.shard_layout import (ShardLayout, densify_keyed_snapshot,
                                          has_shard_slices, slice_manifest)
from flink_tpu.windowing.assigners import TumblingEventTimeWindows

WINDOW_MS = 1000


def _digests(out):
    """Exact per-fired-batch fingerprint: window, row count, raw BYTES of
    the emitted key and result columns (order included)."""
    return [(int(np.asarray(b.column("window_start"))[0]), len(b),
             np.asarray(b.column("k")).tobytes(),
             np.asarray(b.column("result")).tobytes())
            for b in out if hasattr(b, "columns") and "result" in b.columns]


def _counters(op):
    """The per-operator counters ``job_status()`` surfaces."""
    c = {
        "late_dropped": op.late_dropped,
        "num_keys": op.key_index.num_keys if op.key_index else 0,
        "watermark": op.watermark,
        "last_fired_window": op.last_fired_window,
        "device_health": op.device_health_stats(),
    }
    if op.paging_stats() is not None:
        p = op.paging_stats()
        # residency split is a per-shard-run scheduling detail; the key
        # population and capacity are the invariants
        c["paging"] = {"capacity": p["capacity"],
                       "total_keys": p["resident_keys"] + p["spilled_keys"]}
    return c


def _mk(D, emit_tier="host", device_sync="scatter", paging=None, **kw):
    if paging is not None:
        emit_tier = "device"
    kw.setdefault("key_column", "k")
    kw.setdefault("value_column", "v")
    kw.update(emit_tier=emit_tier,
              snapshot_source="mirror" if emit_tier == "host" else "device",
              device_sync=device_sync if emit_tier == "host" else "scatter",
              paging=paging)
    if D == 1:
        op = WindowAggOperator(TumblingEventTimeWindows.of(WINDOW_MS),
                               SumAggregator(jnp.float32), **kw)
    else:
        op = MeshWindowAggOperator(TumblingEventTimeWindows.of(WINDOW_MS),
                                   SumAggregator(jnp.float32),
                                   mesh=make_mesh(D), **kw)
    op.open(RuntimeContext())
    return op


def _run(op, seed=3, n_batches=6, nk=3000, B=4096, snap_at=None,
         late_every=0):
    """Seeded feed with per-batch watermarks (and optional late records),
    an optional mid-run snapshot, ending with end_input."""
    rng = np.random.default_rng(seed)
    out, snap = [], None
    for i in range(n_batches):
        k = rng.integers(0, nk, B).astype(np.int64)
        v = rng.random(B).astype(np.float32)
        ts = i * 500 + np.sort(rng.integers(0, 500, B)).astype(np.int64)
        if late_every and i and i % late_every == 0:
            ts[: B // 8] -= 2500          # beyond-lateness drops
        out += op.process_batch(RecordBatch({"k": k, "v": v}, timestamps=ts))
        out += op.process_watermark(Watermark(int(ts.max()) - 1))
        if snap_at == i:
            op.prepare_snapshot_pre_barrier()
            snap = op.snapshot_state()
    out += op.end_input()
    return _digests(out), snap, _counters(op)


# ---------------------------------------------------------------------------
# tier invariance: mesh sizes 1 vs 2 vs 4, bit-identical digests + counters
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tier,sync", [("host", "scatter"),
                                       ("host", "deferred"),
                                       ("device", "scatter")])
def test_mesh_size_invariance_by_tier(tier, sync):
    ref, _, ref_counters = _run(_mk(1, tier, sync), late_every=3)
    assert len(ref) >= 3
    for D in (2, 4):
        got, _, counters = _run(_mk(D, tier, sync), late_every=3)
        assert got == ref, f"digests diverge at mesh size {D} ({tier}/{sync})"
        assert counters == ref_counters, f"counters diverge at D={D}"


def test_mesh_deferred_refresh_keeps_state_pre_partitioned():
    """``device_refresh`` (deferred sync's sync point) must hand back
    PRE-partitioned state: its out shardings equal the update step's in
    shardings, so chained dispatches never reshard."""
    op = _mk(4, "host", "deferred")
    rng = np.random.default_rng(0)
    for i in range(3):
        k = rng.integers(0, 2000, 4096).astype(np.int64)
        op.process_batch(RecordBatch(
            {"k": k, "v": np.ones(4096, np.float32)},
            timestamps=np.full(4096, i * 300, np.int64)))
        op.process_watermark(Watermark(i * 300))
    assert op._device_stale
    assert op.verify_mirror()          # refresh + round-trip compare
    assert not op._device_stale
    assert len(op._leaves[0].sharding.device_set) == 4


def test_mesh_paging_invariance_64k_cap_256k_keys():
    """The PR-2 acceptance shape on the mesh: 256k keys through a 64k-row
    resident ring, digest- and counter-identical at mesh sizes 1 vs 2."""
    kw = dict(seed=5, n_batches=10, nk=1 << 18, B=1 << 15)
    ref, _, ref_counters = _run(
        _mk(1, paging=PagingConfig(capacity=1 << 16)), **kw)
    got, _, counters = _run(
        _mk(2, paging=PagingConfig(capacity=1 << 16)), **kw)
    assert got == ref
    assert counters == ref_counters
    # the key population genuinely exceeded the resident capacity
    assert ref_counters["paging"]["total_keys"] > 1 << 16


# ---------------------------------------------------------------------------
# snapshot rescale: N shards -> M shards, both directions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d_from,d_to", [(4, 2), (2, 4), (4, 1), (1, 4)])
def test_mesh_snapshot_rescales_between_mesh_sizes(d_from, d_to):
    _, snap, _ = _run(_mk(d_from), snap_at=3)
    assert snap is not None
    if d_from > 1:
        assert has_shard_slices(snap)
        man = slice_manifest(snap)
        assert [m["shard"] for m in man] == list(range(d_from))
        lo = 0
        for m in man:          # slices tile [0, n) in shard order
            assert m["row_range"][0] == lo
            lo = m["row_range"][1]
    # reference tail: restore at the WRITER's size and replay
    ref_op = _mk(d_from)
    ref_op.restore_state(snap)
    ref_tail, _, _ = _run(ref_op, seed=99, n_batches=3)
    # rescaled tail must be bit-identical
    op2 = _mk(d_to)
    op2.restore_state(snap)
    tail, _, _ = _run(op2, seed=99, n_batches=3)
    assert tail == ref_tail


@pytest.mark.parametrize("d_from,d_to", [(1, 2), (2, 1)])
def test_mesh_paged_snapshot_rescales(d_from, d_to):
    """Paged snapshots (dense gid-indexed: the gid space exceeds K_cap, so
    slices don't apply) restore across mesh sizes in both directions."""
    cap = PagingConfig(capacity=2048)
    kw = dict(seed=5, n_batches=6, nk=6000, B=1024)
    _, snap, _ = _run(_mk(d_from, paging=cap), snap_at=3, **kw)
    assert snap is not None and not has_shard_slices(snap)
    ref_op = _mk(d_from, paging=PagingConfig(capacity=2048))
    ref_op.restore_state(snap)
    ref_tail, _, _ = _run(ref_op, seed=99, n_batches=2, nk=6000, B=1024)
    op2 = _mk(d_to, paging=PagingConfig(capacity=2048))
    op2.restore_state(snap)
    tail, _, _ = _run(op2, seed=99, n_batches=2, nk=6000, B=1024)
    assert tail == ref_tail


def test_densify_round_trip_and_validation():
    layout = ShardLayout(4, 64)
    counts = np.arange(50 * 2, dtype=np.int32).reshape(50, 2)
    leaves = [np.random.default_rng(0).random((50, 2)).astype(np.float32)]
    from flink_tpu.state.shard_layout import split_to_shard_slices
    snap = split_to_shard_slices({"counts": counts, "leaves": leaves},
                                 layout)
    assert has_shard_slices(snap)
    dense = densify_keyed_snapshot(snap)
    assert np.array_equal(dense["counts"], counts)
    assert np.array_equal(dense["leaves"][0], leaves[0])
    # a tampered manifest (gap) fails loudly instead of silently dropping
    bad = dict(snap)
    bad["shard_slices"] = [s for s in snap["shard_slices"]
                           if s["shard"] != 1]
    with pytest.raises(ValueError, match="tile"):
        densify_keyed_snapshot(bad)


# ---------------------------------------------------------------------------
# compile-once: the pjit'd step never recompiles at fixed geometry
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("D", [2, 4])
def test_mesh_step_compiles_once_per_geometry(D):
    """Driving many batches of one geometry through the sharded step adds
    EXACTLY one compiled variant — an implicit reshard (out_shardings !=
    next in_shardings) or a geometry leak would mint more."""
    op = _mk(D, "device")
    if op.mesh_step_cache_size() < 0:
        pytest.skip("jax build without the jit cache probe")
    rng = np.random.default_rng(0)
    nk, B = 1500, 2048
    # insert every key first so K never grows mid-measurement
    warm_k = np.pad(np.arange(nk, dtype=np.int64), (0, B - nk),
                    mode="edge")
    op.process_batch(RecordBatch(
        {"k": warm_k, "v": np.zeros(B, np.float32)},
        timestamps=np.zeros(B, np.int64)))
    steady_k = rng.integers(0, nk, B).astype(np.int64)
    op.process_batch(RecordBatch(
        {"k": steady_k, "v": np.ones(B, np.float32)},
        timestamps=np.full(B, 10, np.int64)))
    size_after_warm = op.mesh_step_cache_size()
    for i in range(5):
        # random VALUES, fixed geometry and key set: the exchange capacity
        # high-water is already established, so zero recompiles are legal
        op.process_batch(RecordBatch(
            {"k": steady_k, "v": rng.random(B).astype(np.float32)},
            timestamps=np.full(B, 20 + i, np.int64)))
    assert op.mesh_step_cache_size() == size_after_warm, \
        "sharded update step recompiled at fixed geometry (reshard leak?)"


def test_mesh_per_shard_probe_breakdown_populated():
    """The host tier's fused probe reports per-shard wall times aligned
    with the mesh (the probe_mirror wall decomposed into D independent
    probes).  Requires the native mirror (sharded C pass)."""
    from flink_tpu.native import native_available
    if not native_available():
        pytest.skip("native library unavailable")
    op = _mk(2, "host")
    rng = np.random.default_rng(0)
    B = 1 << 15   # >= the C pass's parallel threshold
    for i in range(3):
        op.process_batch(RecordBatch(
            {"k": rng.integers(0, 5000, B).astype(np.int64),
             "v": np.ones(B, np.float32)},
            timestamps=np.full(B, i, np.int64)))
    op.flush_pipeline()
    assert "probe_mirror" in op.phase_shard_ns
    per_shard = op.phase_shard_ns["probe_mirror"]
    assert per_shard.size >= 2 and int(per_shard.sum()) > 0


# ---------------------------------------------------------------------------
# the staged batch goes to the mesh as it is (ISSUE 31): the destination
# shard is derived inside the step, the answers stay the one-chip fold's
# ---------------------------------------------------------------------------

ROUTE_COUNTERS = ("exchange_route_batches", "exchange_route_copied",
                  "exchange_cap_counts_skipped")


def _route_counters(op):
    return tuple(op.phase_bytes.get(k, 0) for k in ROUTE_COUNTERS)


def _mk_routed(D, agg="sum", **kw):
    """Device-tier operator: the one-chip one (``D`` None) or the mesh one
    at ``D`` shards (1 included: a mesh of one device runs the exchange)."""
    from flink_tpu.core.functions import (AvgAggregator, CountAggregator,
                                          MaxAggregator, MinAggregator,
                                          TupleAggregator)
    if agg == "sum":
        kw.update(agg=SumAggregator(jnp.float32), value_column="v")
    elif agg == "tuple4":                  # four leaves: the whole row rides
        kw.update(agg=TupleAggregator({    # (k and ts int64, v f32)
            "total": ("v", SumAggregator(jnp.float32)),
            "n": ("v", CountAggregator()),
            "lo": ("v", MinAggregator(jnp.float32)),
            "hi": ("v", MaxAggregator(jnp.float32))}),
            value_selector=lambda c: {n: c[n] for n in ("k", "ts", "v")})
    elif agg == "sql5":                    # the SQL plan's shape (cell 6):
        need = ("__ones", "hi_in", "lo_in", "mean_in", "total_in")
        kw.update(agg=TupleAggregator({    # a column a call + int32 ones
            "total": ("total_in", SumAggregator(jnp.float32)),
            "n": ("__ones", CountAggregator()),
            "lo": ("lo_in", MinAggregator(jnp.float32)),
            "hi": ("hi_in", MaxAggregator(jnp.float32)),
            "mean": ("mean_in", AvgAggregator(jnp.float32))}),
            value_selector=lambda c: {n: c[n] for n in need})
    else:                                  # "mixed": an int8 leaf cannot
        kw.update(agg=TupleAggregator({    # ride as a 4-byte word
            "total": ("v", SumAggregator(jnp.float32)),
            "mean": ("flag", AvgAggregator(jnp.float32))}),
            value_selector=lambda c: {n: c[n] for n in ("flag", "v")})
    kw.update(key_column="k", emit_tier="device", snapshot_source="device")
    assigner = TumblingEventTimeWindows.of(WINDOW_MS)
    if D is None:
        op = WindowAggOperator(assigner, **kw)
    else:
        op = MeshWindowAggOperator(assigner, mesh=make_mesh(D), **kw)
    op.open(RuntimeContext())
    return op


#: per aggregate of ``_mk_routed``: value leaves that ride in the packed
#: array, and beside it
ROUTED_LEAVES = {"sum": (1, 0), "tuple4": (3, 0), "sql5": (5, 0),
                 "mixed": (1, 1)}


def _routed_batch(k, v, ts):
    """The columns every aggregate of ``_mk_routed`` selects from."""
    cols = {"k": k, "v": v, "ts": ts,
            "flag": (k % 7 - 3).astype(np.int8),
            "__ones": np.ones(k.size, np.int32)}
    cols.update({f"{a}_in": v for a in ("total", "lo", "hi", "mean")})
    return RecordBatch(cols, timestamps=ts)


def _all_digests(out):
    """Every column of every fired batch, bytes and order included."""
    return [[(c, np.asarray(b.column(c)).tobytes()) for c in sorted(b.columns)]
            for b in out if hasattr(b, "columns")]


def _state_bytes(op):
    """The snapshot's dense cells (counts and every leaf), as bytes."""
    op.prepare_snapshot_pre_barrier()
    snap = densify_keyed_snapshot(op.snapshot_state())
    return (np.asarray(snap["counts"]).tobytes(),
            [np.asarray(l).tobytes() for l in snap["leaves"]],
            np.asarray(snap["panes"]).tobytes())


def _drive_routed(op, sizes=(777, 1024, 1024, 300, 2048, 777), nk=900):
    """Batches of uneven sizes over several panes: 777 stages to 1024 ids
    of which 247 are the base class's ``_PAD_ID`` rows, 1024 and 2048
    stage full; skew differs batch to batch (uniform, then all keys on one
    shard's slots, then a few hot keys)."""
    rng = np.random.default_rng(17)
    out, state = [], None
    for i, B in enumerate(sizes):
        k = (rng.integers(0, nk, B), rng.integers(0, nk // 8, B),
             rng.integers(0, 5, B))[i % 3].astype(np.int64)
        v = rng.random(B).astype(np.float32)
        ts = i * 400 + np.sort(rng.integers(0, 400, B)).astype(np.int64)
        out += op.process_batch(_routed_batch(k, v, ts))
        out += op.process_watermark(Watermark(int(ts.max()) - 1))
        if i == 3:
            state = _state_bytes(op)
    out += op.end_input()
    return _all_digests(out), state


@functools.lru_cache(maxsize=None)
def _one_chip(agg):
    return _drive_routed(_mk_routed(None, agg))


@pytest.mark.parametrize("agg", ["sum", "tuple4", "sql5", "mixed"])
@pytest.mark.parametrize("D", [1, 2, 3, 4])
def test_staged_batch_routes_bit_identically_to_one_chip(D, agg):
    """State and fired rows of the mesh fold equal the one-chip operator's
    to the byte at D = 1, 2, 4 and at D = 3 (where no staged power of two
    divides by D, so every batch takes the padded copy), with ``_PAD_ID``
    rows in most batches, whatever rides in the packed array (ISSUE 38):
    one f32 leaf, int64 columns narrowed at the pack, the SQL plan's five
    leaves, an int8 leaf that rides beside it."""
    ref_fired, ref_state = _one_chip(agg)
    op = _mk_routed(D, agg)
    fired, state = _drive_routed(op)
    assert len(ref_fired) >= 3
    assert fired == ref_fired, f"fired rows diverge at D={D} ({agg})"
    assert state == ref_state, f"state diverges at D={D} ({agg})"
    routed, copied, skipped = _route_counters(op)
    assert routed == op.fused_stats()["hot_dispatches"] == 6
    assert copied == (routed if D == 3 else 0)
    # D = 1: one block is the whole batch, its one pair sends all of it
    assert skipped <= routed - 1
    # one array a batch where every leaf packs, one more for each that
    # cannot; the counts are read off the leaves' dtype and shape
    packed, beside = ROUTED_LEAVES[agg]
    counted = op.phase_bytes
    assert counted["exchange_packed_leaves"] == packed * routed
    assert counted["exchange_unpacked_leaves"] == beside * routed
    assert counted["exchange_value_leaves"] == (packed + beside) * routed
    assert counted["exchange_h2d_arrays"] == (1 + beside) * routed
    # one geometry, one program: the same batch again compiles nothing
    if op.mesh_step_cache_size() >= 0:
        rng = np.random.default_rng(D)
        k = rng.integers(0, 900, 1024).astype(np.int64)
        again = _routed_batch(k, rng.random(1024).astype(np.float32),
                              np.full(1024, 9 * WINDOW_MS, np.int64))
        op.process_batch(again)
        size = op.mesh_step_cache_size()
        for _ in range(3):
            op.process_batch(again)
        assert op.mesh_step_cache_size() == size


def test_the_pack_narrows_a_wide_column_as_device_put_does():
    """An int64 or f64 leaf is written into the packed words by numpy's
    cast; that has to be, bit for bit, what ``device_put`` makes of the
    column with x64 off (values past 32 bits included)."""
    rng = np.random.default_rng(38)
    wide = np.concatenate([rng.integers(-2**62, 2**62, 500),
                           [2**31, -2**31 - 1, 2**32 + 5, -1, 0]])
    for col in (wide.astype(np.int64), wide.astype(np.float64) / 3):
        dt = jax.dtypes.canonicalize_dtype(col.dtype)
        on_device = np.asarray(jax.device_put(col))
        words = np.empty(col.size, np.int32)
        words.view(dt)[...] = col
        assert on_device.dtype == dt
        assert words.tobytes() == on_device.tobytes()


@pytest.mark.parametrize("agg", ["tuple4", "mixed"])
@pytest.mark.parametrize("D", [2, 3])
def test_a_packed_buffer_is_not_rewritten_under_a_running_step(D, agg):
    """Twelve batches of distinct content back to back through one
    operator, no read between them: the CPU backend may alias a host
    buffer into the transfer, so a packed buffer (or a staging set)
    handed out again before the step that read it has finished would fold
    another batch's rows.  The answer is the one-chip operator's, and the
    pool stays at its bound."""
    nk, B = 600, 1024

    def drive(op):
        rng = np.random.default_rng(12)
        for i in range(12):
            k = rng.integers(0, nk, B).astype(np.int64)
            v = (rng.random(B) * (i + 1)).astype(np.float32)
            op.process_batch(_routed_batch(k, v, np.full(B, i, np.int64)))
        return _state_bytes(op), _all_digests(
            op.process_watermark(Watermark(WINDOW_MS)))

    want = drive(_mk_routed(None, agg, initial_key_capacity=1024))
    op = _mk_routed(D, agg, initial_key_capacity=1024)
    assert drive(op) == want
    pools = list(op._packed_pool.values())
    assert pools and all(1 <= len(p) <= 4 for p in pools)
    assert op.phase_bytes["exchange_route_batches"] == 12
    # the gate is a step OUTPUT that no later step donates: it reads ready
    # once the state is (the counts themselves are deleted by then, which
    # reads as never ready), so buffers and staging sets do come back
    jax.block_until_ready(op._counts)
    assert all(pk.ready() for pool in pools for pk in pool)
    assert all(st.ready() for pool in op._staging_pool.values()
               for st in pool)


def test_the_packed_pool_hands_out_only_finished_buffers():
    """``_packed_acquire``: a buffer whose token is not ready (or was
    deleted: unknowable) is passed over, a finished one comes back, and
    past four in flight a buffer is made and not kept."""
    class Token:
        def __init__(self, done):
            self.done = done

        def is_ready(self):
            if self.done is None:
                raise RuntimeError("Array has been deleted.")
            return self.done

    op = _mk_routed(2)
    shape = (2, 2, 512)
    first = op._packed_acquire(shape)
    assert first.words.shape == shape and first.words.dtype == np.int32
    first.token = Token(False)
    second = op._packed_acquire(shape)
    assert second is not first
    second.token = Token(None)
    assert op._packed_acquire((2, 3, 512)).words.shape == (2, 3, 512)
    first.token.done = True
    assert op._packed_acquire(shape) is first and first.token is None
    held = [first]
    for _ in range(5):
        first.token = Token(False)
        held.append(op._packed_acquire(shape))
        held[-1].token = Token(False)
    assert len({id(pk) for pk in held}) == len(held)
    assert len(op._packed_pool[shape]) == 4


@pytest.mark.parametrize("D,n", [(4, 1022), (4, 1024), (3, 1024), (3, 1023),
                                 (2, 65)])
def test_update_step_takes_any_staged_length(D, n):
    """The override reads what it needs off the buffer: a length that
    divides by D goes uncopied, any other is padded; ``_PAD_ID`` rows may
    sit anywhere in it.  Cells equal the one-chip step's on the same ids."""
    from flink_tpu.operators.window_agg import _PAD_ID
    nk = 512
    ops = [_mk_routed(None, initial_key_capacity=nk),
           _mk_routed(D, initial_key_capacity=nk)]
    rng = np.random.default_rng(n)
    warm = RecordBatch({"k": np.arange(nk, dtype=np.int64),
                        "v": np.zeros(nk, np.float32)},
                       timestamps=np.zeros(nk, np.int64))
    slots = rng.integers(0, nk, n)
    ids = (slots * ops[0]._P).astype(np.int32)     # pane 0 of every slot
    ids[rng.random(n) < 0.2] = _PAD_ID             # pads mid-batch
    vals = rng.random(n).astype(np.float32)
    fired = []
    for op in ops:
        op.process_batch(warm)
        assert op._P == ops[0]._P
        before = _route_counters(op)
        res = op._launch_update(ids.copy(), vals.copy())
        op._leaves, op._counts = res[0], res[1]
        fired.append(_digests(op.process_watermark(Watermark(WINDOW_MS))))
    assert fired[0] == fired[1] and len(fired[0]) == 1
    total = np.frombuffer(fired[1][0][3], np.float32).sum(dtype=np.float64)
    assert abs(total - vals[ids != _PAD_ID].sum(dtype=np.float64)) < 1e-3
    routed, copied, _skipped = (
        a - b for a, b in zip(_route_counters(ops[1]), before))
    assert (routed, copied) == (1, 1 if n % D else 0)


@pytest.mark.parametrize("D,K,P", [(2, 64, 16), (4, 64, 16), (3, 96, 16),
                                   (6, 192, 4), (4, 64, 3)])
def test_pair_counts_is_the_device_rule(D, K, P):
    """The host's capacity count against the destination rule written out
    row by row: a live id goes to the shard owning its slot, a padding row
    to its row index mod D (spans that are and are not powers of two)."""
    from flink_tpu.operators.window_agg import _PAD_ID
    op = MeshWindowAggOperator.__new__(MeshWindowAggOperator)
    op.n_shards, op._K, op._P = D, K, P
    rng = np.random.default_rng(K + D)
    block = 40
    ids = rng.integers(0, K * P, (D, block)).astype(np.int32)
    ids[rng.random((D, block)) < 0.3] = _PAD_ID
    want = np.zeros((D, D), np.int64)
    for s in range(D):
        for r in range(block):
            i = int(ids[s, r])
            want[s, (i // P) // (K // D) if i < K * P else r % D] += 1
    got = op._pair_counts(ids, ids < K * P)
    assert np.array_equal(got, want) and got.sum() == D * block


def test_capacity_count_taken_below_the_ceiling_and_skipped_at_it():
    """The sticky capacity: counted from the ids while it is below a
    source block's length, not at all once it equals it (no pair can send
    more), and ONE compiled step per batch geometry across batches whose
    skew differs — capacity only grows."""
    D, B, nk = 4, 2048, 2048
    op = _mk_routed(D, initial_key_capacity=nk)
    if op.mesh_step_cache_size() < 0:
        pytest.skip("jax build without the jit cache probe")
    rng = np.random.default_rng(4)

    def feed(keys, t):
        op.process_batch(RecordBatch(
            {"k": keys.astype(np.int64),
             "v": rng.random(keys.size).astype(np.float32)},
            timestamps=np.full(keys.size, t, np.int64)))

    def counters():
        return tuple(int(a - b) for a, b in zip(_route_counters(op), base))

    # every key once, 64 a batch (slots in arrival order: a first batch of
    # 2048 new keys would send each source block to its own shard, the
    # ceiling at once): blocks of 16 rows leave the capacity at 16
    for i in range(nk // 64):
        feed(np.arange(i * 64, (i + 1) * 64), 0)
    assert op._K == nk and op._exchange_cap_hw == 16
    base = _route_counters(op)
    assert base == (nk // 64, 0, nk // 64 - 1)
    block = B // D
    size = op.mesh_step_cache_size()
    # below the ceiling every batch is counted; less skew than the
    # high-water mark compiles nothing
    feed(rng.integers(0, nk, B), 1)
    cap_even = op._exchange_cap_hw
    assert B // (D * D) <= cap_even < block
    assert op.mesh_step_cache_size() == size + 1
    for t in range(2, 5):
        feed(rng.permutation(nk), t)
    assert counters() == (4, 0, 0)
    assert op._exchange_cap_hw >= cap_even
    grown = op._exchange_cap_hw > cap_even
    assert op.mesh_step_cache_size() == size + 1 + int(grown)
    # a source block whose rows all go to one shard: the ceiling
    feed(rng.integers(0, nk // D, B), 5)
    assert op._exchange_cap_hw == block
    assert counters() == (5, 0, 0)
    size = op.mesh_step_cache_size()
    # at the ceiling: nothing counted, nothing compiled, whatever the skew
    for t, keys in enumerate((rng.permutation(nk),
                              rng.integers(0, nk // D, B),
                              rng.integers(0, 7, B),
                              rng.integers(0, nk, B - 300)), 6):
        feed(keys, t)
    assert counters() == (9, 0, 4)
    assert op._exchange_cap_hw == block
    assert op.mesh_step_cache_size() == size
    # and the answers are all there
    out = op.process_watermark(Watermark(WINDOW_MS))
    assert sum(len(b) for b in out if hasattr(b, "columns")) == nk


def _drive_hot_key(op, nk, B):
    """One batch geometry: every key once (slots in arrival order, so each
    source block goes to one shard and the capacity is at its ceiling from
    the first step), then batches in which one key takes most rows with
    values whose f32 sum depends on the order of the additions."""
    rng = np.random.default_rng(33)
    out = op.process_batch(RecordBatch(
        {"k": np.arange(nk, dtype=np.int64), "v": np.ones(nk, np.float32)},
        timestamps=np.zeros(nk, np.int64)))
    for t in range(1, 5):
        k = np.where(rng.random(B) < 0.7, 7, rng.integers(0, nk, B))
        v = (rng.random(B) * 10.0 ** rng.integers(-6, 7, B))
        out += op.process_batch(RecordBatch(
            {"k": k.astype(np.int64), "v": v.astype(np.float32)},
            timestamps=np.full(B, t, np.int64)))
    state = _state_bytes(op)
    out += op.process_watermark(Watermark(WINDOW_MS))
    return _all_digests(out), state


@pytest.mark.parametrize("D", [2, 4])
def test_a_hot_key_sums_in_batch_order_through_one_program(D):
    """ISSUE 33: the closed-form bucket plan keeps a destination's rows in
    batch order, so a key that recurs some 1,400 times a batch sums to the
    one-chip fold's f32 bit for bit (an f64 sum rounds elsewhere), and a
    steady run is ONE compiled ``_mesh_update_step``."""
    nk = B = 2048
    ref_fired, ref_state = _drive_hot_key(
        _mk_routed(None, initial_key_capacity=nk), nk, B)
    op = _mk_routed(D, initial_key_capacity=nk)
    before = op.mesh_step_cache_size()
    fired, state = _drive_hot_key(op, nk, B)
    assert len(fired) >= 1
    assert fired == ref_fired and state == ref_state
    assert op._exchange_cap_hw == B // D
    if before >= 0:
        assert op.mesh_step_cache_size() == before + 1


# ---------------------------------------------------------------------------
# device-lane health on the mesh: whole-mesh degrade, bit-exact
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_mesh_quarantine_degrades_whole_mesh_bit_exactly():
    """PR-4's WedgedDevice nemesis at mesh size 2: a watchdog quarantine
    mid-run degrades the WHOLE mesh to the host tier (state materializes
    shard-by-shard into the host value mirror), fires continue without a
    dropped record, a checkpoint completes DURING quarantine, and the
    healed device re-promotes at the checkpoint-aligned safe point — with
    fire digests value-identical to an unfaulted pass (the degraded tier
    emits the mirror's f64 twins, so digests compare exact f64 sums, the
    PR-4 acceptance fingerprint)."""
    from flink_tpu.runtime import device_health as dh
    from flink_tpu.testing import chaos

    def vdigests(out):
        return [(int(np.asarray(b.column("window_start"))[0]), len(b),
                 np.asarray(b.column("k")).tobytes(),
                 float(np.asarray(b.column("result"), np.float64).sum()))
                for b in out if hasattr(b, "columns")
                and "result" in b.columns]

    def one_pass(inject):
        prev = dh.get_monitor(create=False)
        dh.set_monitor(dh.DeviceHealthMonitor(
            dh.WatchdogConfig(deadline_floor_s=0.5), heal_async=False))
        inj = chaos.FaultInjector(seed=3)
        sched = (inj.inject("device.dispatch", chaos.WedgedDevice(at=8))
                 if inject else None)
        op = _mk(2, "device")
        rng = np.random.default_rng(7)
        out = []
        snap_degraded = False
        try:
            with chaos.installed(inj):
                for i in range(24):
                    k = rng.integers(0, 64, 512).astype(np.int64)
                    v = np.ones(512, np.float32)
                    ts = i * 500 + np.sort(
                        rng.integers(0, 500, 512)).astype(np.int64)
                    out += op.process_batch(
                        RecordBatch({"k": k, "v": v}, timestamps=ts))
                    out += op.process_watermark(Watermark(int(ts.max()) - 1))
                    if inject and i == 12:
                        op.prepare_snapshot_pre_barrier()
                        snap = op.snapshot_state()
                        snap_degraded = op._degraded
                        assert "counts" in densify_keyed_snapshot(snap)
                        sched.heal()
                        dh.get_monitor().probe_now()
                    if inject and i == 16:
                        out += op.prepare_snapshot_pre_barrier()
                out += op.end_input()
            stats = op.device_health_stats()
            mon = dh.get_monitor().status()
            op.close()
        finally:
            dh.set_monitor(prev)
        return vdigests(out), stats, mon, snap_degraded

    clean, _, _, _ = one_pass(False)
    wedged, stats, mon, snap_degraded = one_pass(True)
    assert clean == wedged and len(clean) >= 10
    assert snap_degraded, "checkpoint during quarantine did not run degraded"
    assert mon["quarantines"] == 1 and mon["heals"] == 1
    assert stats["quarantine_migrations"] == 1
    assert stats["repromotions"] == 1 and stats["degraded"] == 0


@pytest.mark.slow
def test_mesh_1m_key_tumbling_sum_identical_to_single_chip():
    """The acceptance run at north-star cardinality: the sharded hot path
    at mesh size 2 produces fire digests BIT-identical to the single-chip
    run on the 1M-key tumbling sum."""
    kw = dict(seed=7, n_batches=12, nk=1 << 20, B=1 << 17)
    ref, _, ref_counters = _run(
        _mk(1, "host", initial_key_capacity=1 << 20), **kw)
    got, _, counters = _run(
        _mk(2, "host", initial_key_capacity=1 << 20), **kw)
    assert got == ref and len(ref) >= 5
    assert counters == ref_counters
    # ~1.57M draws over the 2^20 key space: ~0.8M distinct keys live
    assert ref_counters["num_keys"] > 800_000
