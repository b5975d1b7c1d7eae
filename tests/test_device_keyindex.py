"""Device-resident key probe (ISSUE 7 tentpole contract).

``WindowAggOperator(device_probe=...)`` resolves warm keys ON the device,
inside the jitted step, via ``state/device_keyindex.py``: warm-row
contributions accumulate in mirror-precision delta arrays and the host C
pass touches only misses.  The probe is a pure scheduling/placement change:
fire digests, snapshots, and counters must be BIT-identical with the probe
on vs off — on the host tier under both sync cadences, with the numpy
mirror fallback, under paging, across mesh sizes, and through a mid-batch
WedgedDevice quarantine.  Steady state (a second pass over identical keys)
must show ZERO host fold work via the miss counters, and capacity must be
sticky: exactly one XLA compile per (table capacity, K_cap, batch
geometry).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flink_tpu.core.batch import RecordBatch, Watermark
from flink_tpu.core.functions import RuntimeContext, SumAggregator
from flink_tpu.operators.window_agg import WindowAggOperator
from flink_tpu.state.keyindex import KeyIndex
from flink_tpu.state.device_keyindex import DeviceKeyIndex, lax_probe
from flink_tpu.windowing.assigners import TumblingEventTimeWindows


def _mk_op(device_probe="off", emit_tier="host", device_sync="scatter",
           native=True, paging=None, **kw):
    if paging is not None:
        emit_tier = "device"
    op = WindowAggOperator(
        TumblingEventTimeWindows.of(100), SumAggregator(jnp.float32),
        key_column="k", value_column="v", emit_tier=emit_tier,
        snapshot_source="mirror" if emit_tier == "host" else "device",
        device_sync=device_sync if emit_tier == "host" else "scatter",
        native_emit=native, paging=paging, device_probe=device_probe, **kw)
    op.open(RuntimeContext())
    return op


def _digests(out):
    return [(int(np.asarray(b.column("window_start"))[0]), len(b),
             np.asarray(b.column("k")).tobytes(),
             np.asarray(b.column("result")).tobytes())
            for b in out if hasattr(b, "columns") and "result" in b.columns]


def _counters(op):
    return {
        "late_dropped": op.late_dropped,
        "num_keys": op.key_index.num_keys if op.key_index else 0,
        "watermark": op.watermark,
        "last_fired_window": op.last_fired_window,
    }


def _assert_snap_equal(a, b):
    assert set(a) == set(b), set(a) ^ set(b)
    for k in sorted(a):
        va, vb = a[k], b[k]
        if isinstance(va, np.ndarray):
            assert np.array_equal(va, np.asarray(vb)), k
        elif isinstance(va, (list, tuple)):
            for x, y in zip(va, vb):
                assert np.array_equal(np.asarray(x), np.asarray(y)), k
        elif isinstance(va, dict):
            continue  # key_index internals: covered by digest equality
        else:
            assert va == vb, k


def _seeded_run(op, n_batches=10, nk=1500, b=4000, seed=11, snap_at=6):
    rng = np.random.default_rng(seed)
    out, snap = [], None
    for i in range(n_batches):
        keys = rng.integers(0, nk, b).astype(np.int64)
        vals = rng.random(b).astype(np.float32)
        ts = i * 50 + np.sort(rng.integers(0, 50, b)).astype(np.int64)
        out += op.process_batch(RecordBatch({"k": keys, "v": vals},
                                            timestamps=ts))
        out += op.process_watermark(Watermark(int(ts.max()) - 1))
        if i == snap_at:
            op.prepare_snapshot_pre_barrier()
            snap = op.snapshot_state()
    out += op.end_input()
    counters = _counters(op)
    return _digests(out), snap, counters


# ---------------------------------------------------------------------------
# the table itself
# ---------------------------------------------------------------------------

def test_lax_probe_matches_keyindex_lookup(rng):
    keys = rng.integers(-2 ** 62, 2 ** 62, 5000).astype(np.int64)
    keys = np.concatenate([keys, keys[:700]])          # duplicates
    ki = KeyIndex()
    ki.lookup_or_insert(keys)
    dki = DeviceKeyIndex(initial_capacity=1 << 10)     # forces growth
    assert dki.ensure_loaded(ki) == ki.num_keys
    klo, khi, start = dki.prepare_batch(keys)
    got = np.asarray(jax.jit(lax_probe)(
        *dki.table(), jnp.asarray(klo), jnp.asarray(khi),
        jnp.asarray(start)))
    assert np.array_equal(got, ki.lookup(keys))
    # unseen keys miss
    unk = rng.integers(2 ** 62, 2 ** 63 - 1, 200).astype(np.int64)
    klo, khi, start = dki.prepare_batch(unk)
    got = np.asarray(jax.jit(lax_probe)(
        *dki.table(), jnp.asarray(klo), jnp.asarray(khi),
        jnp.asarray(start)))
    assert np.array_equal(got, ki.lookup(unk))


def test_incremental_insert_and_sticky_growth(rng):
    ki = KeyIndex()
    dki = DeviceKeyIndex(initial_capacity=1 << 10)
    cap_seen = []
    for wave in range(4):
        keys = rng.integers(0, 1 << 40, 2000).astype(np.int64)
        ki.lookup_or_insert(keys)
        dki.ensure_loaded(ki)
        cap_seen.append(dki.capacity)
        klo, khi, start = dki.prepare_batch(keys)
        got = np.asarray(jax.jit(lax_probe)(
            *dki.table(), jnp.asarray(klo), jnp.asarray(khi),
            jnp.asarray(start)))
        assert np.array_equal(got, ki.lookup(keys)), f"wave {wave}"
    # sticky pow2 high-water: never shrinks, always a power of two
    assert all(c & (c - 1) == 0 for c in cap_seen)
    assert cap_seen == sorted(cap_seen)
    assert ki.num_keys <= dki.capacity // 2  # load factor <= 0.5 held


# ---------------------------------------------------------------------------
# digest equality: probe on vs off, every tier
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sync", ["scatter", "deferred"])
def test_host_tier_bit_identical_probe_on_off(sync):
    ref = _seeded_run(_mk_op("off", device_sync=sync))
    got = _seeded_run(_mk_op("on", device_sync=sync))
    assert got[0] == ref[0], f"fire digests diverged under {sync}"
    _assert_snap_equal(got[1], ref[1])
    assert got[2] == ref[2]


def test_numpy_mirror_fallback_bit_identical():
    """native_emit=False pins the numpy value mirror: the delta applies
    through the numpy twin instead of wm_apply_delta — same digests."""
    ref = _seeded_run(_mk_op("off", native=False))
    got = _seeded_run(_mk_op("on", native=False))
    assert got[0] == ref[0]
    _assert_snap_equal(got[1], ref[1])
    assert got[2] == ref[2]


def test_steady_state_zero_host_fold_work(rng):
    """The acceptance assertion: a second pass over IDENTICAL keys must
    resolve entirely on device — the host C fold touches zero rows (the
    miss counters do not move)."""
    op = _mk_op("on")
    keys = rng.integers(0, 4096, 8192).astype(np.int64)
    vals = rng.random(8192).astype(np.float32)
    op.process_batch(RecordBatch(
        {"k": keys, "v": vals},
        timestamps=np.full(8192, 10, np.int64)))
    s1 = op.device_probe_stats()
    assert s1["enabled"] and s1["probe_misses"] == 8192  # empty table
    op.process_batch(RecordBatch(
        {"k": keys, "v": vals},
        timestamps=np.full(8192, 20, np.int64)))
    s2 = op.device_probe_stats()
    assert s2["probe_misses"] == s1["probe_misses"], \
        "second pass over identical keys reached the host fold"
    assert s2["probe_hits"] == s1["probe_hits"] + 8192
    assert s2["miss_inserts"] == op.key_index.num_keys
    out = op.process_watermark(Watermark(10_000))
    total = sum(float(np.asarray(b.column("result"), np.float64).sum())
                for b in out if hasattr(b, "columns"))
    assert total == pytest.approx(2.0 * float(vals.astype(np.float64).sum()))
    op.close()


def test_restore_into_probe_off_operator_and_back():
    """Snapshots are probe-agnostic: a probe-on snapshot restores into a
    probe-off operator (and vice versa) with identical remainder fires."""
    rng = np.random.default_rng(5)
    batches = []
    for i in range(8):
        keys = rng.integers(0, 1000, 3000).astype(np.int64)
        vals = rng.random(3000).astype(np.float32)
        ts = i * 50 + np.sort(rng.integers(0, 50, 3000)).astype(np.int64)
        batches.append((keys, vals, ts))

    def run_from(op, start, out):
        for keys, vals, ts in batches[start:]:
            out += op.process_batch(RecordBatch({"k": keys, "v": vals},
                                                timestamps=ts))
            out += op.process_watermark(Watermark(int(ts.max()) - 1))
        out += op.end_input()
        return _digests(out)

    for src_probe in ("on", "off"):
        src = _mk_op(src_probe)
        for keys, vals, ts in batches[:4]:
            src.process_batch(RecordBatch({"k": keys, "v": vals},
                                          timestamps=ts))
            src.process_watermark(Watermark(int(ts.max()) - 1))
        src.prepare_snapshot_pre_barrier()
        mid = src.snapshot_state()
        # the SAME snapshot restored under either probe mode must replay
        # the remainder identically (restored state is f32-cast either
        # way, so restored-vs-restored is the apples-to-apples compare)
        runs = {}
        for dst_probe in ("on", "off"):
            dst = _mk_op(dst_probe)
            dst.restore_state(mid)
            runs[dst_probe] = run_from(dst, 4, [])
        assert runs["on"] == runs["off"], \
            f"restore of a probe-{src_probe} snapshot diverged by probe mode"


# ---------------------------------------------------------------------------
# paging: the probe is structurally ineligible there (gid->row translation
# is host work per batch) — requesting it must degrade to OFF, not break
# ---------------------------------------------------------------------------

def test_paging_64k_cap_256k_keys_probe_request_is_noop():
    from flink_tpu.state.paging import PagingConfig

    def run(device_probe, tmp):
        op = _mk_op(device_probe,
                    paging=PagingConfig(capacity=1 << 16, directory=tmp))
        rng = np.random.default_rng(3)
        out = []
        n_keys = 1 << 18
        for i in range(4):
            keys = rng.integers(0, n_keys, 1 << 15).astype(np.int64)
            vals = rng.random(1 << 15).astype(np.float32)
            ts = i * 50 + np.sort(
                rng.integers(0, 50, 1 << 15)).astype(np.int64)
            out += op.process_batch(RecordBatch({"k": keys, "v": vals},
                                                timestamps=ts))
            out += op.process_watermark(Watermark(int(ts.max()) - 1))
        out += op.end_input()
        stats = op.device_probe_stats()
        op.close()
        return _digests(out), stats

    import tempfile
    with tempfile.TemporaryDirectory() as t1, \
            tempfile.TemporaryDirectory() as t2:
        ref, _ = run("off", t1)
        got, stats = run("on", t2)
    assert got == ref
    assert stats["enabled"] == 0 and stats["probe_hits"] == 0


# ---------------------------------------------------------------------------
# mesh: one logical operator, probe on vs off at mesh 1 v 2
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sync", ["scatter", "deferred"])
def test_mesh_1v2_bit_identical_probe_on_off(sync):
    from flink_tpu.parallel.mesh import make_mesh
    from flink_tpu.parallel.mesh_runtime import MeshWindowAggOperator

    def mk(device_probe, D):
        op = MeshWindowAggOperator(
            TumblingEventTimeWindows.of(100), SumAggregator(jnp.float32),
            key_column="k", value_column="v", emit_tier="host",
            snapshot_source="mirror", device_sync=sync,
            device_probe=device_probe, mesh=make_mesh(D),
            initial_key_capacity=2048)
        op.open(RuntimeContext(max_parallelism=128))
        return op

    ref = _seeded_run(mk("off", 1), n_batches=6)
    for D in (1, 2):
        got = _seeded_run(mk("on", D), n_batches=6)
        assert got[0] == ref[0], f"mesh x{D} fire digests diverged"
        assert got[2] == ref[2]


# ---------------------------------------------------------------------------
# quarantine: mid-batch WedgedDevice with the probe active
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_mid_batch_wedge_quarantine_digest_identical():
    from flink_tpu.runtime import device_health as dh
    from flink_tpu.testing import chaos

    rng = np.random.default_rng(7)
    batches = []
    for i in range(20):
        k = rng.integers(0, 64, 512).astype(np.int64)
        v = np.ones(512, np.float32)
        ts = i * 50 + np.sort(rng.integers(0, 50, 512)).astype(np.int64)
        batches.append((k, v, ts))

    def one_pass(device_probe, inject):
        prev = dh.get_monitor(create=False)
        dh.set_monitor(dh.DeviceHealthMonitor(
            dh.WatchdogConfig(deadline_floor_s=0.5), heal_async=False))
        inj = chaos.FaultInjector(seed=3)
        sched = (inj.inject("device.dispatch", chaos.WedgedDevice(at=8))
                 if inject else None)
        op = _mk_op(device_probe)
        out = []
        snap_degraded = False
        try:
            with chaos.installed(inj):
                for i, (k, v, ts) in enumerate(batches):
                    out += op.process_batch(
                        RecordBatch({"k": k, "v": v}, timestamps=ts))
                    out += op.process_watermark(Watermark(int(ts.max()) - 1))
                    if inject and i == 12:
                        op.prepare_snapshot_pre_barrier()
                        op.snapshot_state()   # checkpoint DURING quarantine
                        snap_degraded = op._degraded
                        sched.heal()
                        dh.get_monitor().probe_now()
                    if inject and i == 16:
                        out += op.prepare_snapshot_pre_barrier()
                out += op.end_input()
            stats = op.device_health_stats()
            op.close()
        finally:
            dh.set_monitor(prev)
        return _digests(out), stats, snap_degraded

    clean, _s, _d = one_pass("off", False)
    wedged, stats, snap_degraded = one_pass("on", True)
    assert wedged == clean, "wedged probe-on run diverged from clean run"
    assert stats["quarantine_migrations"] == 1
    assert stats["repromotions"] == 1 and stats["degraded"] == 0
    assert snap_degraded, "snapshot did not run during quarantine"


# ---------------------------------------------------------------------------
# compile discipline: sticky capacity, one compile per geometry
# ---------------------------------------------------------------------------

def test_compile_once_per_table_capacity_and_geometry(rng):
    # pre-sized K: key growth is a LEGITIMATE recompile (K_cap is part of
    # the geometry), so pin it to isolate the sticky-table-capacity claim
    op = _mk_op("on", initial_key_capacity=4096)
    base = op.devprobe_step_cache_size()["_probed_update_step"]
    if base < 0:
        pytest.skip("jax without the jit cache probe")
    keys = rng.integers(0, 2000, 4096).astype(np.int64)
    for i in range(6):
        vals = rng.random(4096).astype(np.float32)
        ts = np.full(4096, 10 + i, np.int64)
        op.process_batch(RecordBatch({"k": keys, "v": vals}, timestamps=ts))
    sizes = op.devprobe_step_cache_size()
    # same keys, same geometry, capacity sticky: exactly ONE compile
    assert sizes["_probed_update_step"] - base == 1, sizes
    cap0 = op._dki.capacity
    # force a capacity growth: a burst of fresh keys past the load factor.
    # The growth batch itself compiles once at the OLD capacity (its probe
    # ran before the misses inserted) with the new batch geometry, and the
    # first steady batch compiles once at the NEW (capacity, K) — then the
    # cache must go quiet.
    many = rng.integers(1 << 40, 1 << 41, 40_000).astype(np.int64)
    for i in range(4):
        op.process_batch(RecordBatch(
            {"k": many, "v": np.ones(many.size, np.float32)},
            timestamps=np.full(many.size, 20 + i, np.int64)))
    assert op._dki.capacity > cap0
    grown = op.devprobe_step_cache_size()["_probed_update_step"]
    assert grown - sizes["_probed_update_step"] == 2, \
        "sticky capacity failed: steady state kept recompiling"
    op.close()


def test_device_probe_stats_surface():
    op = _mk_op("on")
    s = op.device_probe_stats()
    assert set(s) >= {"enabled", "probe_hits", "probe_misses",
                      "miss_inserts", "delta_syncs", "probe_hit_rate",
                      "delta_d2h_bytes"}
    op.process_batch(RecordBatch(
        {"k": np.arange(100, dtype=np.int64),
         "v": np.ones(100, np.float32)},
        timestamps=np.full(100, 10, np.int64)))
    op.process_watermark(Watermark(1000))
    s = op.device_probe_stats()
    assert s["enabled"] == 1
    assert s["probe_hits"] + s["probe_misses"] == 100
    assert s["delta_d2h_bytes"] >= 0
    op.close()
