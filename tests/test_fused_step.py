"""One-dispatch fused megastep (ISSUE 11 tentpole contract).

``WindowAggOperator(superbatch=N)`` stages up to N micro-batches and
advances them in ONE pass — a device-side ``lax.scan`` over donated state
buffers when the device-resident probe is active, a single concatenated
fused C probe+fold on the host tier otherwise.  Staging is a pure
scheduling change: fire digests, snapshot bytes, and counters must be
BIT-identical fused on vs off — on the host tier under both sync
cadences, with the numpy-mirror fallback, at mesh 1 vs 2, and through a
mid-scan WedgedDevice quarantine (the scan is one transactional
``guarded_dispatch``).  Geometry must be sticky: exactly one XLA compile
of the scan megastep per (table capacity, K_cap, P, depth, step width,
value spec).  Paging keeps the lane structurally off, like the probe.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flink_tpu.core.batch import RecordBatch, Watermark
from flink_tpu.core.functions import RuntimeContext, SumAggregator
from flink_tpu.operators import fused_step
from flink_tpu.operators.window_agg import WindowAggOperator
from flink_tpu.windowing.assigners import TumblingEventTimeWindows


def _mk_op(superbatch=0, device_probe="off", emit_tier="host",
           device_sync="deferred", native=True, paging=None,
           pipeline_depth=0, **kw):
    if paging is not None:
        emit_tier = "device"
    op = WindowAggOperator(
        TumblingEventTimeWindows.of(100), SumAggregator(jnp.float32),
        key_column="k", value_column="v", emit_tier=emit_tier,
        snapshot_source="mirror" if emit_tier == "host" else "device",
        device_sync=device_sync if emit_tier == "host" else "scatter",
        native_emit=native, paging=paging, device_probe=device_probe,
        superbatch=superbatch, pipeline_depth=pipeline_depth, **kw)
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


def _snap_bytes(snap):
    return (snap["counts"].tobytes(),
            tuple(np.asarray(l).tobytes() for l in snap["leaves"]))


def _seeded_run(op, n_batches=12, nk=1500, b=4000, seed=11, snap_at=6,
                close=True):
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
    if close:
        op.close()
    return _digests(out), snap, counters


# ---------------------------------------------------------------------------
# bit-identity: fused on/off across tiers and lanes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sync", ["deferred", "scatter"])
def test_host_tier_bit_identical_fused_on_off(sync):
    ref = _seeded_run(_mk_op(1, device_sync=sync))
    got = _seeded_run(_mk_op(4, device_sync=sync))
    assert got[0] == ref[0], "fire digests diverged"
    assert _snap_bytes(got[1]) == _snap_bytes(ref[1]), "snapshot diverged"
    assert got[2] == ref[2], "counters diverged"


@pytest.mark.parametrize("sync", ["deferred", "scatter"])
def test_scan_lane_bit_identical(sync):
    """The forced scan lane (device probe ON + superbatch) must match the
    fully-unfused path — and must actually have scanned."""
    ref = _seeded_run(_mk_op(1, device_probe="off", device_sync=sync))
    op = _mk_op(4, device_probe="on", device_sync=sync)
    got_d, got_s, got_c = _seeded_run(op, close=False)
    fu = op.fused_stats()
    op.close()
    assert got_d == ref[0] and got_c == ref[2]
    assert _snap_bytes(got_s) == _snap_bytes(ref[1])
    assert fu["scan_dispatches"] > 0, "scan lane never dispatched"
    assert fu["scan_steps"] > fu["scan_dispatches"], \
        "scan dispatches did not amortize multiple staged steps"


def test_numpy_mirror_fallback_bit_identical():
    ref = _seeded_run(_mk_op(1, native=False))
    got = _seeded_run(_mk_op(4, native=False))
    assert got[0] == ref[0] and got[2] == ref[2]
    assert _snap_bytes(got[1]) == _snap_bytes(ref[1])


def test_pipelined_fused_bit_identical():
    ref = _seeded_run(_mk_op(1))
    got = _seeded_run(_mk_op(4, pipeline_depth=1))
    assert got[0] == ref[0] and got[2] == ref[2]
    assert _snap_bytes(got[1]) == _snap_bytes(ref[1])


def test_mesh_1v2_bit_identical_fused_on_off():
    from flink_tpu.parallel.mesh import make_mesh
    from flink_tpu.parallel.mesh_runtime import MeshWindowAggOperator

    def mk(superbatch, D):
        op = MeshWindowAggOperator(
            TumblingEventTimeWindows.of(100), SumAggregator(jnp.float32),
            key_column="k", value_column="v", emit_tier="host",
            snapshot_source="mirror", device_sync="deferred",
            superbatch=superbatch, mesh=make_mesh(D),
            initial_key_capacity=2048)
        op.open(RuntimeContext(max_parallelism=128))
        return op

    ref = _seeded_run(mk(1, 1), n_batches=6)
    for D in (1, 2):
        got = _seeded_run(mk(4, D), n_batches=6)
        assert got[0] == ref[0], f"mesh x{D} fire digests diverged"
        assert got[2] == ref[2]


def test_paging_keeps_fused_lane_structurally_off():
    """Paging pins the device emit tier, and the fused lane stages the
    HOST tier only — a superbatch request on a paged operator degrades
    gracefully to off (like the device probe), digests unchanged."""
    from flink_tpu.state.paging import PagingConfig

    def run(superbatch):
        op = _mk_op(superbatch, paging=PagingConfig(capacity=1024))
        res = _seeded_run(op, nk=2000, close=False)
        fu = op.fused_stats()
        op.close()
        return res, fu

    (ref, fu1), (got, fu4) = run(1), run(4)
    assert got[0] == ref[0]
    assert fu4["enabled"] == 0 and fu4["staged_batches"] == 0
    assert fu1["enabled"] == 0


# ---------------------------------------------------------------------------
# staging semantics: fire boundaries flush, plain watermarks stage
# ---------------------------------------------------------------------------

def test_watermark_fast_path_keeps_batches_staged():
    """A watermark that passes no window end must leave the stage parked
    (the amortization source); the one that crosses a fire boundary must
    flush and fire — and a snapshot must flush too."""
    op = _mk_op(8)
    rng = np.random.default_rng(5)
    out = []
    # first window fires so last_fired_window is set (fast-path arming)
    k = rng.integers(0, 64, 512).astype(np.int64)
    v = np.ones(512, np.float32)
    out += op.process_batch(RecordBatch(
        {"k": k, "v": v}, timestamps=np.full(512, 50, np.int64)))
    out += op.process_watermark(Watermark(99))
    assert _digests(out), "first window did not fire"
    staged_seen = 0
    for i in range(3):   # all inside window [100, 200): no boundary
        ts = 100 + i * 20 + np.sort(
            rng.integers(0, 20, 512)).astype(np.int64)
        op.process_batch(RecordBatch({"k": k, "v": v}, timestamps=ts))
        got = op.process_watermark(Watermark(int(ts.max()) - 1))
        assert got == []
        staged_seen = max(staged_seen, op.fused_stats()["staged_pending"])
    assert staged_seen >= 2, "watermarks flushed the stage prematurely"
    fired = op.process_watermark(Watermark(199))   # boundary: flush + fire
    assert _digests(fired), "boundary watermark did not fire"
    assert op.fused_stats()["staged_pending"] == 0
    # snapshot flushes staged rows: state must contain them
    op.process_batch(RecordBatch(
        {"k": k, "v": v}, timestamps=np.full(512, 250, np.int64)))
    assert op.fused_stats()["staged_pending"] == 1
    op.prepare_snapshot_pre_barrier()
    snap = op.snapshot_state()
    assert op.fused_stats()["staged_pending"] == 0
    assert snap["counts"].sum() >= 512, "snapshot missed staged rows"
    op.close()


def test_restore_fused_into_unfused_and_back():
    """A snapshot written mid-stream by either lane restores into the
    other, and the replayed tail produces identical digests."""
    rng = np.random.default_rng(13)
    batches = []
    for i in range(12):
        keys = rng.integers(0, 900, 3000).astype(np.int64)
        vals = rng.random(3000).astype(np.float32)
        ts = i * 50 + np.sort(rng.integers(0, 50, 3000)).astype(np.int64)
        batches.append((keys, vals, ts))

    def drain(op, subset):
        out = []
        for keys, vals, ts in subset:
            out += op.process_batch(RecordBatch({"k": keys, "v": vals},
                                                timestamps=ts))
            out += op.process_watermark(Watermark(int(ts.max()) - 1))
        out += op.end_input()
        return _digests(out)

    def snapshot_from(src_sb):
        src = _mk_op(src_sb)
        for keys, vals, ts in batches[:6]:
            src.process_batch(RecordBatch({"k": keys, "v": vals},
                                          timestamps=ts))
            src.process_watermark(Watermark(int(ts.max()) - 1))
        src.prepare_snapshot_pre_barrier()
        snap = src.snapshot_state()
        src.close()
        return snap

    snaps = {sb: snapshot_from(sb) for sb in (1, 4)}
    # the fused writer's snapshot is byte-identical to the unfused one
    assert _snap_bytes(snaps[4]) == _snap_bytes(snaps[1])
    ref = None
    for src_sb, dst_sb in ((1, 1), (4, 1), (1, 4), (4, 4)):
        dst = _mk_op(dst_sb)
        dst.restore_state(snaps[src_sb])
        got = drain(dst, batches[6:])
        dst.close()
        if ref is None:
            ref = got
        assert got == ref, f"restore {src_sb}->{dst_sb} diverged"


# ---------------------------------------------------------------------------
# compile discipline: sticky [N, B] geometry
# ---------------------------------------------------------------------------

def test_scan_compiles_once_per_sticky_geometry(rng):
    op = _mk_op(4, device_probe="on", initial_key_capacity=4096)
    nk = 1000
    keys0 = rng.integers(0, nk, 2048).astype(np.int64)
    op.process_batch(RecordBatch(
        {"k": keys0, "v": np.ones(2048, np.float32)},
        timestamps=np.zeros(2048, np.int64)))
    op.flush_pipeline()   # table capacity settles before the smoke
    base = op.fused_step_cache_size()["_fused_scan_delta_step"]
    if base < 0:
        pytest.skip("jax build without the jit cache-size probe")
    # wobbling batch sizes UNDER the sticky high-waters must not recompile
    for i in range(1, 9):
        b = 2048 - 64 * i
        keys = rng.integers(0, nk, b).astype(np.int64)
        ts = np.full(b, i * 10, np.int64)
        op.process_batch(RecordBatch(
            {"k": keys, "v": np.ones(b, np.float32)}, timestamps=ts))
    op.flush_pipeline()
    got = op.fused_step_cache_size()["_fused_scan_delta_step"]
    assert got <= base + 1, \
        f"scan step recompiled per batch: {base} -> {got}"
    op.close()


# ---------------------------------------------------------------------------
# quarantine: a wedged scan is transactional; donated buffers stay safe
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_mid_scan_wedge_quarantine_digest_identical():
    from flink_tpu.runtime import device_health as dh
    from flink_tpu.testing import chaos

    rng = np.random.default_rng(7)
    batches = []
    for i in range(20):
        k = rng.integers(0, 64, 512).astype(np.int64)
        v = np.ones(512, np.float32)
        ts = i * 50 + np.sort(rng.integers(0, 50, 512)).astype(np.int64)
        batches.append((k, v, ts))

    def one_pass(superbatch, device_probe, inject):
        prev = dh.get_monitor(create=False)
        dh.set_monitor(dh.DeviceHealthMonitor(
            dh.WatchdogConfig(deadline_floor_s=0.5), heal_async=False))
        inj = chaos.FaultInjector(seed=3)
        sched = (inj.inject("device.dispatch", chaos.WedgedDevice(at=3))
                 if inject else None)
        op = _mk_op(superbatch, device_probe=device_probe)
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
            held_deleted = any(
                getattr(a, "is_deleted", lambda: False)()
                for a in ((op._delta_counts,) + (op._delta_leaves or ()))
                if a is not None)
            op.close()
        finally:
            dh.set_monitor(prev)
        return _digests(out), stats, snap_degraded, held_deleted

    clean, _s, _d, _h = one_pass(1, "off", False)
    wedged, stats, snap_degraded, held = one_pass(4, "on", True)
    assert wedged == clean, "wedged scan run diverged from clean run"
    assert stats["quarantine_migrations"] == 1
    assert stats["repromotions"] == 1 and stats["degraded"] == 0
    assert snap_degraded, "snapshot did not run during quarantine"
    assert not held, "operator still holds deleted (donated) delta arrays"


def test_donated_delta_consumed_takes_restart_path():
    """PR-4's donated-buffer guard, extended to the scan lane's delta
    planes: when a genuinely timed-out dispatch already CONSUMED the
    donated delta arrays, the degrade path must refuse in-process salvage
    (a use-after-free) and surface the original error — the restart path
    — instead of limping on with deleted arrays."""
    op = _mk_op(4, device_probe="on")
    rng = np.random.default_rng(3)
    for i in range(8):
        k = rng.integers(0, 64, 256).astype(np.int64)
        ts = i * 50 + np.sort(rng.integers(0, 50, 256)).astype(np.int64)
        op.process_batch(RecordBatch(
            {"k": k, "v": np.ones(256, np.float32)}, timestamps=ts))
        op.process_watermark(Watermark(int(ts.max()) - 1))
    op.flush_pipeline()
    assert op._delta_counts is not None and op._delta_panes, \
        "test setup: scan lane left no unsynced delta"
    # simulate the donated-consumed state a real watchdog timeout leaves
    for a in (op._delta_counts, *op._delta_leaves):
        a.delete()
    from flink_tpu.runtime.device_health import DeviceQuarantinedError
    err = DeviceQuarantinedError("wedged (test)")
    with pytest.raises(DeviceQuarantinedError) as ei:
        op._devprobe_degrade(err)
    assert ei.value is err, "restart path must surface the ORIGINAL error"
    assert "consumed" in str(ei.value.__cause__ or "").lower() \
        or isinstance(ei.value.__cause__, RuntimeError)
    op.close()


# ---------------------------------------------------------------------------
# resolution / calibration plumbing
# ---------------------------------------------------------------------------

def test_superbatch_zero_resolves_via_calibration(monkeypatch):
    calls = []
    monkeypatch.setattr(fused_step, "calibrated_superbatch",
                        lambda: calls.append(1) or 6)
    op = _mk_op(0)
    res = _seeded_run(op, n_batches=6, close=False)
    fu = op.fused_stats()
    op.close()
    assert calls, "auto superbatch never consulted the calibration"
    assert fu["depth"] == 6 and fu["enabled"] == 1
    ref = _seeded_run(_mk_op(1), n_batches=6)
    assert res[0] == ref[0], "auto-resolved staging diverged"


def test_superbatch_env_override(monkeypatch):
    monkeypatch.setenv("FLINK_TPU_SUPERBATCH", "3")
    fused_step._reset_calibration_for_tests()
    try:
        assert fused_step.calibrated_superbatch() == 3
    finally:
        fused_step._reset_calibration_for_tests()


def test_single_batch_flush_is_not_a_super_pass():
    """A fire boundary draining ONE staged batch runs the plain per-batch
    path: ``host_super_passes`` must count genuine multi-batch passes
    only (the mesh amortization story reads this counter), while
    ``flushes`` counts every drain."""
    op = _mk_op(4)
    rng = np.random.default_rng(3)
    for i in range(5):
        keys = rng.integers(0, 512, 1024).astype(np.int64)
        vals = rng.random(1024).astype(np.float32)
        # each batch spans a whole window: every watermark fires, so the
        # stage never accumulates past one batch
        ts = np.full(1024, i * 100 + 50, np.int64)
        op.process_batch(RecordBatch({"k": keys, "v": vals},
                                     timestamps=ts))
        op.process_watermark(Watermark(i * 100 + 99))
    fu = op.fused_stats()
    op.close()
    assert fu["flushes"] >= 5
    assert fu["host_super_passes"] == 0, \
        "single-batch drains must not count as super passes"


def test_count_trigger_pins_unfused():
    """Count triggers read device counts inside process_batch: they must
    never stage (the per-batch read IS the semantics)."""
    from flink_tpu.windowing.assigners import GlobalWindows
    from flink_tpu.windowing.triggers import CountTrigger

    op = WindowAggOperator(
        GlobalWindows(), SumAggregator(jnp.float32), key_column="k",
        value_column="v", trigger=CountTrigger.of(4), superbatch=8)
    op.open(RuntimeContext())
    k = np.arange(16, dtype=np.int64) % 4
    out = []
    for i in range(4):
        out += op.process_batch(RecordBatch(
            {"k": k, "v": np.ones(16, np.float32)},
            timestamps=np.full(16, i * 10, np.int64)))
    assert op.fused_stats()["enabled"] == 0
    assert any(hasattr(b, "columns") for b in out), "count fire missing"
    op.close()


def test_fused_scan_phase_and_span_names():
    """The --profile/tracing contract under fusion: scan-lane time lands
    in a 'fused_scan' phase whose hot_stage spans ride the journal under
    the phase's span name (the test_bench_gate vocabulary scrape sees the
    literal in window_agg.py)."""
    from flink_tpu.observability import tracing
    from flink_tpu.operators.window_agg import phase_span_name

    j = tracing.install(tracing.SpanJournal(capacity=4096))
    try:
        op = _mk_op(4, device_probe="on")
        _seeded_run(op, n_batches=6)
    finally:
        tracing.uninstall()
    assert op.phase_ns.get("fused_scan", 0) > 0, \
        "scan-lane time not attributed to the fused_scan phase"
    names = {s[3] for s in j.snapshot()["spans"] if s[4] == "hot_stage"}
    assert phase_span_name("fused_scan") == "window_agg.fused_scan"
    assert "window_agg.fused_scan" in names, \
        "no fused_scan hot_stage spans emitted"
