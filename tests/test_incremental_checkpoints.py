"""Incremental + changelog checkpoints (ISSUE-16).

The acceptance contract: checkpoint bytes scale with the CHANGE RATE, not
the state size (at <=10% of keys churning an increment is <=25% of the
full snapshot), restore = base + ordered increment replay is bit-identical
to a full-snapshot restore — on every state tier (device / host-mirror /
paged), across savepoints (always full, never advancing the chain), under
lost notifies (union-of-unconfirmed dirt), through the content-addressed
storage's compaction, and past torn increment writes (CRC-gated fallback
to an older base).
"""

import os
import time

import numpy as np
import jax.numpy as jnp
import pytest

from flink_tpu.core.batch import RecordBatch, Watermark
from flink_tpu.core.functions import RuntimeContext, SumAggregator
from flink_tpu.operators.base import snapshot_scope
from flink_tpu.operators.window_agg import WindowAggOperator
from flink_tpu.runtime.checkpoint import delta
from flink_tpu.runtime.checkpoint.incremental import \
    IncrementalCheckpointStorage
from flink_tpu.runtime.checkpoint.local import TaskLocalStateStore
from flink_tpu.runtime.checkpoint.storage import CorruptCheckpointError
from flink_tpu.state.changelog import ChangelogKeyedStateBackend
from flink_tpu.state.heap import HeapKeyedStateBackend
from flink_tpu.testing.chaos import (FailTimes, FaultInjector,
                                     TruncatedWrite, installed)
from flink_tpu.windowing import TumblingEventTimeWindows


def make_op(**kw):
    op = WindowAggOperator(TumblingEventTimeWindows.of(1000),
                           SumAggregator(jnp.float32),
                           key_column="k", value_column="v", **kw)
    op.open(RuntimeContext())
    op.incremental_state = True
    return op


def feed(op, keys, vals, ts, wm=None):
    out = op.process_batch(RecordBatch(
        {"k": np.asarray(keys), "v": np.asarray(vals, np.float32)},
        timestamps=np.asarray(ts, np.int64)))
    if wm is not None:
        out += op.process_watermark(Watermark(wm))
    return out


def collect(elements):
    rows = {}
    for b in elements:
        if not hasattr(b, "columns") or "result" not in b.columns:
            continue
        for i in range(len(b)):
            rows[(int(np.asarray(b.column("k"))[i]),
                  int(np.asarray(b.column("window_start"))[i]))] = float(
                np.asarray(b.column("result"))[i])
    return rows


def cut(op, cid, incremental=True):
    """One checkpoint cut as the runtime takes it (scoped snapshot)."""
    with snapshot_scope(cid, incremental=incremental):
        return op.snapshot_state()


def tree_equal(a, b, path="$"):
    """Bit-exact structural equality of two snapshot trees."""
    if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype, f"{path}: dtype {a.dtype} != {b.dtype}"
        assert a.shape == b.shape, f"{path}: shape {a.shape} != {b.shape}"
        assert np.array_equal(a, b), f"{path}: values differ"
        return
    if isinstance(a, dict):
        assert isinstance(b, dict) and a.keys() == b.keys(), \
            f"{path}: keys {sorted(map(str, a))} != {sorted(map(str, b))}"
        for k in a:
            tree_equal(a[k], b[k], f"{path}.{k}")
        return
    if isinstance(a, (list, tuple)):
        assert isinstance(b, (list, tuple)) and len(a) == len(b), \
            f"{path}: length {len(a)} != {len(b)}"
        for i, (x, y) in enumerate(zip(a, b)):
            tree_equal(x, y, f"{path}[{i}]")
        return
    assert a == b, f"{path}: {a!r} != {b!r}"


def _traffic(seed=3, n_seed=3000, churn=120, rounds=3):
    """Seed a key population, then rounds of sparse churn batches."""
    rng = np.random.default_rng(seed)
    seed_keys = np.repeat(np.arange(n_seed), 1)
    batches = [(seed_keys, np.ones(seed_keys.size, np.float32),
                np.full(seed_keys.size, 100, np.int64))]
    for _ in range(rounds):
        k = rng.integers(0, churn, 400)
        batches.append((k, np.ones(400, np.float32),
                        np.full(400, 100, np.int64)))
    return batches


# ---------------------------------------------------------------------------
# window_delta increments: digest-identical restore
# ---------------------------------------------------------------------------

def _restore_digest_identical(op_kw):
    """Chain restore (base + increments) == full restore, bit-identical,
    and both continue to identical fires."""
    batches = _traffic()
    op = make_op(**op_kw)
    feed(op, *batches[0])
    base = cut(op, 1)
    assert not delta.is_increment(base), "first cut must be a full base"
    op.notify_checkpoint_complete(1)

    chain = [base]
    for i, b in enumerate(batches[1:], start=2):
        feed(op, *b)
        inc = cut(op, i)
        assert delta.is_increment(inc), f"cut {i} did not go incremental"
        op.notify_checkpoint_complete(i)
        chain.append(inc)
    full = op.snapshot_state()            # unscoped: always full

    resolved = delta.resolve_chain(chain)
    tree_equal(resolved, full)

    op_chain, op_full = make_op(**op_kw), make_op(**op_kw)
    op_chain.restore_state(resolved)
    op_full.restore_state(full)
    tree_equal(op_chain.snapshot_state(), op_full.snapshot_state())

    tail = (np.arange(50), np.ones(50, np.float32),
            np.full(50, 100, np.int64))
    got_a = collect(feed(op_chain, *tail, wm=5000))
    got_b = collect(feed(op_full, *tail, wm=5000))
    assert got_a == got_b and got_a, "continued fires diverged"


def test_device_tier_restore_digest_identical():
    _restore_digest_identical({})


def test_host_mirror_tier_restore_digest_identical():
    _restore_digest_identical({"emit_tier": "host"})


def test_paged_tier_restore_digest_identical():
    from flink_tpu.state.paging import PagingConfig
    _restore_digest_identical({"paging": PagingConfig(1 << 10),
                               "initial_key_capacity": 1 << 10,
                               "emit_tier": "device"})


def test_mesh_tier_restore_digest_identical():
    """Sharded mesh state: the increment is cut from the dense mirror and
    applies against the DENSIFIED shard-sliced base, so chain restore
    fires identically to a full-snapshot restore (the resolved tree is
    dense — also the rescale interchange; conftest forces host devices)."""
    from flink_tpu.parallel.mesh import make_mesh
    from flink_tpu.parallel.mesh_runtime import MeshWindowAggOperator

    def mk():
        op = MeshWindowAggOperator(TumblingEventTimeWindows.of(1000),
                                   SumAggregator(jnp.float32),
                                   key_column="k", value_column="v",
                                   mesh=make_mesh(2))
        op.open(RuntimeContext())
        op.incremental_state = True
        return op

    op = mk()
    feed(op, np.arange(500), np.ones(500, np.float32),
         np.full(500, 100, np.int64))
    base = cut(op, 1)
    op.notify_checkpoint_complete(1)
    feed(op, np.arange(40), np.ones(40, np.float32),
         np.full(40, 100, np.int64))
    inc = cut(op, 2)
    assert delta.is_increment(inc)
    full = op.snapshot_state()

    op_a, op_b = mk(), mk()
    op_a.restore_state(delta.resolve_chain([base, inc]))
    op_b.restore_state(full)
    got_a = collect(op_a.process_watermark(Watermark(5000)))
    got_b = collect(op_b.process_watermark(Watermark(5000)))
    assert got_a == got_b and len(got_a) == 500


@pytest.mark.chaos
def test_quarantine_then_incremental_cut_digest_identical():
    """A wedged device degrades the tier MID-CHAIN (the degrade path
    drains the device delta first), so the next increment never depends
    on salvaged device state: chain restore stays digest-identical."""
    from flink_tpu.runtime import device_health as dh
    from flink_tpu.runtime.device_health import (DeviceHealthMonitor,
                                                 WatchdogConfig)
    from flink_tpu.testing import chaos as chaos_mod
    from flink_tpu.testing.chaos import WedgedDevice

    prev = dh.get_monitor(create=False)
    cfg = WatchdogConfig(deadline_floor_s=0.25, first_dispatch_grace_s=30.0,
                         backoff_initial_s=0.001, backoff_max_s=0.01,
                         probe_backoff_initial_s=0.02,
                         probe_backoff_max_s=0.1)
    dh.set_monitor(DeviceHealthMonitor(cfg, heal_async=False))
    try:
        op = make_op(emit_tier="device")
        feed(op, np.arange(500), np.ones(500, np.float32),
             np.full(500, 100, np.int64))
        base = cut(op, 1)
        op.notify_checkpoint_complete(1)

        inj = FaultInjector(seed=9)
        inj.inject("device.dispatch", WedgedDevice(at=0))
        with installed(inj):
            feed(op, np.arange(40), np.ones(40, np.float32),
                 np.full(40, 100, np.int64))    # wedge -> degrade, no loss
        assert op._degraded, "the wedge did not degrade the tier"
        inc = cut(op, 2)                        # cut DURING quarantine
        assert delta.is_increment(inc)
        full = op.snapshot_state()
        tree_equal(delta.resolve_chain([base, inc]), full)
        op_r = make_op()
        op_r.restore_state(delta.resolve_chain([base, inc]))
        got = collect(op_r.process_watermark(Watermark(5000)))
        assert len(got) == 500 and got[(7, 0)] == 2.0
    finally:
        dh.set_monitor(prev if prev is not None and prev.healthy else None)
        chaos_mod.uninstall()


@pytest.mark.chaos
def test_slow_disk_on_increment_append_is_latency_only(tmp_path):
    """A SlowDisk schedule on the store path stalls the append but
    corrupts nothing: backpressure, not data loss — the persisted chain
    still resolves digest-identical."""
    from flink_tpu.testing.chaos import SlowDisk
    inj = FaultInjector(seed=5)
    inj.inject("checkpoint.store",
               SlowDisk(max_s=0.01, min_s=0.002, p=1.0, times=8))
    with installed(inj):
        storage, op, full = _op_chain(tmp_path, n_incs=2, retain=10,
                                      max_increments_per_base=10)
        tree_equal(storage.load_latest(), full)
    assert storage.chain_length(storage.checkpoint_ids()[-1]) == 3


def test_increment_covers_unconfirmed_dirt_after_lost_cut():
    """Crash consistency: a cut whose confirmation never arrives (aborted
    checkpoint, lost notify) stays covered — the NEXT increment ships the
    union of all unconfirmed dirt, so resolving base + inc_3 while
    skipping inc_2 entirely still lands on the exact state."""
    batches = _traffic(seed=11)
    op = make_op()
    feed(op, *batches[0])
    base = cut(op, 1)
    op.notify_checkpoint_complete(1)

    feed(op, *batches[1])
    inc2 = cut(op, 2)                     # frozen but NEVER confirmed
    assert delta.is_increment(inc2)
    feed(op, *batches[2])
    inc3 = cut(op, 3)
    assert delta.is_increment(inc3)
    full = op.snapshot_state()

    tree_equal(delta.resolve_chain([base, inc3]), full)    # 2 lost
    tree_equal(delta.resolve_chain([base, inc2, inc3]), full)  # 2 stored


def test_incremental_bytes_scale_with_change_rate():
    """<=10% of keys churning => increment <= 25% of the full snapshot
    (the acceptance budget; the real ratio is far smaller)."""
    n_keys = 20_000
    op = make_op()
    feed(op, np.arange(n_keys), np.ones(n_keys, np.float32),
         np.full(n_keys, 100, np.int64))
    cut(op, 1)
    op.notify_checkpoint_complete(1)
    churn = np.arange(n_keys // 10)       # 10% of the population
    feed(op, churn, np.ones(churn.size, np.float32),
         np.full(churn.size, 100, np.int64))
    inc = cut(op, 2)
    assert delta.is_increment(inc)
    full = op.snapshot_state()
    ratio = delta.state_size(inc) / delta.state_size(full)
    assert ratio <= 0.25, f"increment is {ratio:.1%} of full"


def test_savepoint_stays_full_and_never_advances_the_chain():
    """A savepoint cut mid-chain ships FULL state, and its notify must not
    advance the operator's confirmed base (the savepoint is out-of-band:
    the increment chain in primary storage never saw it)."""
    op = make_op()
    feed(op, np.arange(2000), np.ones(2000, np.float32),
         np.full(2000, 100, np.int64))
    base = cut(op, 1)
    op.notify_checkpoint_complete(1)
    feed(op, np.arange(100), np.ones(100, np.float32),
         np.full(100, 100, np.int64))
    sp = cut(op, 2, incremental=False)    # savepoint: full, self-contained
    assert not delta.is_increment(sp)
    op.notify_checkpoint_complete(2)      # must NOT re-base the chain
    feed(op, np.arange(100, 200), np.ones(100, np.float32),
         np.full(100, 100, np.int64))
    inc = cut(op, 3)
    assert delta.is_increment(inc)
    # inc still applies against checkpoint 1's base — covering the dirt
    # the savepoint cut saw — because confirmation of cid=2 didn't match
    # any frozen incremental cut
    tree_equal(delta.resolve_chain([base, inc]), op.snapshot_state())


def test_rebase_ratio_forces_a_full_cut():
    """Dirt beyond ``incr_rebase_ratio`` of the dense grid re-bases: the
    cut ships full state (an increment that big stops paying)."""
    op = make_op()
    op.incr_rebase_ratio = 0.5
    feed(op, np.arange(1000), np.ones(1000, np.float32),
         np.full(1000, 100, np.int64))
    cut(op, 1)
    op.notify_checkpoint_complete(1)
    feed(op, np.arange(900), np.ones(900, np.float32),
         np.full(900, 100, np.int64))     # 90% churn
    snap = cut(op, 2)
    assert not delta.is_increment(snap), "90% churn must re-base"


def test_resolved_chain_is_dense_rescale_interchange():
    """The resolved tree IS the dense gid-indexed interchange: key-group
    split/merge on it behaves exactly as on a full snapshot."""
    batches = _traffic(seed=23, n_seed=500, churn=60)
    op = make_op()
    feed(op, *batches[0])
    base = cut(op, 1)
    op.notify_checkpoint_complete(1)
    feed(op, *batches[1])
    inc = cut(op, 2)
    assert delta.is_increment(inc)
    resolved = delta.resolve_chain([base, inc])
    tree_equal(resolved, op.snapshot_state())

    parts = WindowAggOperator.split_snapshot(resolved, max_parallelism=128,
                                             new_parallelism=2)
    merged = WindowAggOperator.merge_snapshots(parts)
    op_m, op_w = make_op(), make_op()
    op_m.restore_state(merged)
    op_w.restore_state(resolved)
    tail = (np.arange(60), np.ones(60, np.float32),
            np.full(60, 100, np.int64))
    assert collect(feed(op_m, *tail, wm=5000)) == \
        collect(feed(op_w, *tail, wm=5000))


# ---------------------------------------------------------------------------
# changelog increments
# ---------------------------------------------------------------------------

def _changelog_backend():
    be = ChangelogKeyedStateBackend(HeapKeyedStateBackend(max_parallelism=16))
    st = be.value_state("v", default=0.0)
    return be, st


def test_changelog_suffix_restore_matches_full():
    """Restore(base + changelog-suffix replay) == restore(full snapshot):
    identical replayed backends, identical reads, identical next cut."""
    be, st = _changelog_backend()
    slots = be.key_slots(np.arange(50))
    st.put_rows(slots, np.arange(50.0))
    be.materialize()
    base = be.snapshot()
    be._unconfirmed.append((1, be._epoch, len(be._log)))
    be.notify_checkpoint_complete(1)

    be.set_current_key(7)
    st.update(700.0)
    inc = be.snapshot_increment(2)
    assert inc is not None and inc["kind"] == "changelog"
    be.notify_checkpoint_complete(2)
    be.set_current_key(9)
    st.update(900.0)
    inc3 = be.snapshot_increment(3)
    assert inc3 is not None and int(inc3["log_base"]) > 0
    full = be.snapshot()

    resolved = delta.resolve_chain([base, inc, inc3])
    # restored-vs-restored: replay the chain-resolved and the full
    # snapshot into twin backends and compare state + continued behavior
    be_a, st_a = _changelog_backend()
    be_a.restore(resolved)
    be_b, st_b = _changelog_backend()
    be_b.restore(full)
    for key, want in ((7, 700.0), (9, 900.0), (3, 3.0)):
        be_a.set_current_key(key)
        be_b.set_current_key(key)
        assert st_a.value() == st_b.value() == want
    tree_equal(be_a.snapshot(), be_b.snapshot())


def test_changelog_increment_spans_lost_cut():
    """The suffix is anchored at the CONFIRMED position: an unconfirmed
    cut in between stays covered by the next increment."""
    be, st = _changelog_backend()
    st_slots = be.key_slots(np.arange(10))
    st.put_rows(st_slots, np.zeros(10))
    base = be.snapshot()
    be._unconfirmed.append((1, be._epoch, len(be._log)))
    be.notify_checkpoint_complete(1)
    be.set_current_key(1)
    st.update(11.0)
    assert be.snapshot_increment(2) is not None    # cut 2: LOST (no notify)
    be.set_current_key(2)
    st.update(22.0)
    inc3 = be.snapshot_increment(3)
    resolved = delta.resolve_chain([base, inc3])   # skipping cut 2
    be_r, st_r = _changelog_backend()
    be_r.restore(resolved)
    be_r.set_current_key(1)
    assert st_r.value() == 11.0                    # cut-2 dirt included
    be_r.set_current_key(2)
    assert st_r.value() == 22.0


def test_changelog_materialization_rebases_the_chain():
    """Auto-materialization re-bases: the cut that crossed the threshold
    ships FULL state (epoch changed), and the chain resumes after."""
    be, st = _changelog_backend()
    be.materialize_threshold = 8
    slots = be.key_slots(np.arange(4))
    st.put_rows(slots, np.zeros(4))
    base = be.snapshot()
    be._unconfirmed.append((1, be._epoch, len(be._log)))
    be.notify_checkpoint_complete(1)
    for i in range(10):                    # outgrow the threshold
        be.set_current_key(i % 4)
        st.update(float(i))
    epoch_before = be._epoch
    assert be.snapshot_increment(2) is None        # re-based: full cut
    assert be._epoch == epoch_before + 1
    full2 = be.snapshot()
    be.notify_checkpoint_complete(2)
    be.set_current_key(0)
    st.update(123.0)
    inc3 = be.snapshot_increment(3)                # chain resumes
    assert inc3 is not None
    be_r, st_r = _changelog_backend()
    be_r.restore(delta.resolve_chain([full2, inc3]))
    be_r.set_current_key(0)
    assert st_r.value() == 123.0


# ---------------------------------------------------------------------------
# durable format: chains in IncrementalCheckpointStorage
# ---------------------------------------------------------------------------

def _op_chain(tmp_path, n_incs=3, **storage_kw):
    """An operator driving real cuts into the storage; returns
    (storage, op, full_snapshot_at_end)."""
    storage = IncrementalCheckpointStorage(str(tmp_path), **storage_kw)
    op = make_op()
    feed(op, np.arange(2000), np.ones(2000, np.float32),
         np.full(2000, 100, np.int64))
    storage.store(1, {"w": cut(op, 1)})
    op.notify_checkpoint_complete(1)
    for i in range(2, 2 + n_incs):
        feed(op, np.arange(50), np.ones(50, np.float32),
             np.full(50, 100, np.int64))
        storage.store(i, {"w": cut(op, i)})
        op.notify_checkpoint_complete(i)
    return storage, op, {"w": op.snapshot_state()}


def test_storage_resolves_increment_chains_on_load(tmp_path):
    storage, op, full = _op_chain(tmp_path, n_incs=3, retain=10,
                                  max_increments_per_base=10)
    last = storage.checkpoint_ids()[-1]
    assert storage.metadata(last)["delta"]
    assert storage.chain_length(last) == 4         # base + 3 increments
    tree_equal(storage.load(last), full)
    tree_equal(storage.load_latest(), full)


def test_storage_compaction_rebases_and_keeps_resolving(tmp_path):
    storage, op, full = _op_chain(tmp_path, n_incs=4, retain=10,
                                  max_increments_per_base=2,
                                  compact_in_background=False)
    ids = storage.checkpoint_ids()
    assert storage.compactions >= 1
    rebased = [i for i in ids if storage.metadata(i).get("compacted")]
    assert rebased, "no checkpoint was re-based in place"
    assert storage.chain_length(rebased[-1]) == 1
    # newer increments chain off the compacted base, not the original
    assert storage.chain_length(ids[-1]) <= 1 + (ids[-1] - rebased[-1])
    tree_equal(storage.load(ids[-1]), full)


def test_retention_never_evicts_a_live_chain_base(tmp_path):
    """retain=2 with a 4-long chain: the base and every link a retained
    head resolves through survive eviction."""
    storage, op, full = _op_chain(tmp_path, n_incs=3, retain=2,
                                  max_increments_per_base=10)
    ids = storage.checkpoint_ids()
    assert 1 in ids, "chain base evicted while increments still need it"
    tree_equal(storage.load(ids[-1]), full)


@pytest.mark.chaos
def test_crash_mid_compaction_restores_from_prior_base(tmp_path):
    """A fault at the compaction rewrite leaves the old chain fully
    intact: the atomic-rename publish never happened, restore still
    resolves base + replay."""
    inj = FaultInjector(seed=5)
    inj.inject("checkpoint.compact", FailTimes(1))
    with installed(inj):
        storage, op, full = _op_chain(tmp_path, n_incs=3, retain=10,
                                      max_increments_per_base=2,
                                      compact_in_background=False)
        last = storage.checkpoint_ids()[-1]
        assert storage.compactions == 0            # faulted attempt
        assert storage.metadata(last)["delta"]     # chain untouched
        tree_equal(storage.load(last), full)
        tree_equal(storage.load_latest(), full)


@pytest.mark.chaos
def test_torn_increment_write_falls_back_to_older_base(tmp_path):
    """TruncatedWrite on the increment append: the CRC/size gate detects
    the torn snapshot at load, and load_latest (the restart-recovery
    path) falls back past it to the newest intact checkpoint."""
    storage = IncrementalCheckpointStorage(str(tmp_path), retain=10,
                                           max_increments_per_base=10)
    op = make_op()
    feed(op, np.arange(2000), np.ones(2000, np.float32),
         np.full(2000, 100, np.int64))
    storage.store(1, {"w": cut(op, 1)})
    op.notify_checkpoint_complete(1)
    intact = {"w": op.snapshot_state()}

    inj = FaultInjector(seed=5)
    inj.inject("checkpoint.increment_append", TruncatedWrite(frac=0.4))
    with installed(inj):
        feed(op, np.arange(50), np.ones(50, np.float32),
             np.full(50, 100, np.int64))
        storage.store(2, {"w": cut(op, 2)})        # torn on disk
    with pytest.raises(CorruptCheckpointError):
        storage.load(2)
    tree_equal(storage.load_latest(), intact)      # fell back to cid 1


@pytest.mark.chaos
def test_materialize_fault_point_fires():
    """``checkpoint.materialize`` is a first-class fault point: a fault
    there fails the cut loudly instead of silently shipping a stale log."""
    from flink_tpu.testing.chaos import InjectedFault
    inj = FaultInjector(seed=5)
    inj.inject("checkpoint.materialize", FailTimes(1))
    be, st = _changelog_backend()
    be.materialize_threshold = 2
    be.key_slots(np.arange(4))
    with installed(inj):
        with pytest.raises(InjectedFault):
            be.snapshot_increment(1)               # auto-materialize faults
    assert inj.fired("checkpoint.materialize") == 1


# ---------------------------------------------------------------------------
# task-local state store: increment chains (local recovery)
# ---------------------------------------------------------------------------

def _local_chain(tmp_path):
    store = TaskLocalStateStore(str(tmp_path), worker_index=0)
    op = make_op()
    feed(op, np.arange(1000), np.ones(1000, np.float32),
         np.full(1000, 100, np.int64))
    store.store(1, "w", 0, cut(op, 1))
    op.notify_checkpoint_complete(1)
    feed(op, np.arange(40), np.ones(40, np.float32),
         np.full(40, 100, np.int64))
    inc = cut(op, 2)
    assert delta.is_increment(inc)
    store.store(2, "w", 0, inc)
    op.notify_checkpoint_complete(2)
    return store, op


def test_local_store_resolves_increment_chains(tmp_path):
    store, op = _local_chain(tmp_path)
    tree_equal(store.load(2, "w", 0), op.snapshot_state())


def test_local_store_confirm_keeps_live_chain_bases(tmp_path):
    """confirm(2) must NOT prune chk-1: checkpoint 2 is an increment whose
    chain still walks through 1.  A later full cut releases it."""
    store, op = _local_chain(tmp_path)
    store.confirm(2)
    assert store.checkpoint_ids() == [1, 2]        # base kept
    tree_equal(store.load(2, "w", 0), op.snapshot_state())
    store.store(3, "w", 0, op.snapshot_state())    # full: chain ends
    store.confirm(3)
    assert store.checkpoint_ids() == [3]


def test_local_store_chain_gap_falls_back_to_remote(tmp_path):
    """A pruned/missing link returns None — the restore silently reads
    the coordinator-shipped remote state instead of a wrong resolve."""
    store, op = _local_chain(tmp_path)
    import shutil
    shutil.rmtree(store._chk_dir(1))               # sever the chain
    assert store.load(2, "w", 0) is None


# ---------------------------------------------------------------------------
# end-to-end: MiniCluster under sub-second incremental cuts
# ---------------------------------------------------------------------------

def test_minicluster_incremental_end_to_end(tmp_path):
    """Sparse churn through the full cluster path: sub-second cuts go
    incremental (delta bytes << full bytes in checkpoint stats), chains
    land in the storage, background compaction re-bases, the restore
    interchange stays dense, and exactly-once totals hold."""
    from flink_tpu.cluster.task import TaskStates
    from flink_tpu.datastream.api import StreamExecutionEnvironment
    from flink_tpu.windowing.assigners import TumblingEventTimeWindows

    rng = np.random.default_rng(7)
    keys = np.concatenate([np.repeat(np.arange(5000), 2),
                           rng.integers(0, 100, 50_000)])
    vals = np.ones(len(keys), np.float32)
    ts = np.full(len(keys), 100, np.int64)
    storage = IncrementalCheckpointStorage(str(tmp_path), retain=4,
                                           max_increments_per_base=4)
    fired_at = []      # wall clock of each subtask's end-of-input fire
    env = StreamExecutionEnvironment()
    env.set_parallelism(2)
    sink = (env.from_collection(columns={"k": keys, "v": vals, "t": ts},
                                batch_size=128)
            .assign_timestamps_and_watermarks(0, timestamp_column="t")
            .key_by("k")
            .window(TumblingEventTimeWindows.of(1000))
            .sum("v")
            .map(lambda cols: (fired_at.append(time.time()), cols)[1])
            .collect())
    res = env.execute_cluster(storage=storage, checkpoint_interval_ms=5,
                              incremental=True)
    assert res.state == TaskStates.FINISHED
    stats = env._last_cluster._checkpoint_stats
    # every window fires once, at the end of input.  A cut that lands
    # between the two subtasks' fires holds the rows one of them fired as
    # the collect sink's state, which no increment describes (88,092 of
    # 90,348 delta bytes in such a cut, the window's increment 808 bytes a
    # subtask as in every other): the steady cut is the last one completed
    # before the first fire.
    incs = [s for s in stats if s.get("incremental")
            and s["completed_at_ms"] + 1 <= min(fired_at) * 1000]
    assert incs, f"no incremental cuts in {len(stats)} checkpoints"
    steady = incs[-1]
    assert steady["delta_bytes"] <= 0.25 * steady["state_size_bytes"], \
        steady
    # the durable chain resolves to a dense, increment-free tree
    snap = storage.load_latest()
    assert snap is not None and not delta.tree_has_increment(snap)
    assert sum(r["v"] for r in sink.rows()) == len(keys)   # exactly-once


def test_minicluster_incremental_via_config(tmp_path):
    """``state.backend.incremental: true`` in the job Configuration flips
    the same wiring on (no explicit kwarg)."""
    from flink_tpu.cluster.minicluster import MiniCluster
    from flink_tpu.config.config_option import Configuration
    from flink_tpu.config.options import StateOptions

    config = Configuration()
    config.set(StateOptions.INCREMENTAL, True)
    mc = MiniCluster(config=config)
    assert mc.incremental


@pytest.mark.slow
def test_process_cluster_incremental_end_to_end(tmp_path):
    """The distributed coordinator: ckpt_opts ship the incremental policy
    with deploy, workers ack increment nodes over the wire, the
    coordinator resolves against the previous cut, increment-capable
    storage persists the raw chain."""
    import sys
    import textwrap

    from flink_tpu.cluster.distributed import ProcessCluster

    mod = tmp_path / "incr_job_mod.py"
    mod.write_text(textwrap.dedent('''
        import numpy as np
        from flink_tpu.datastream.api import StreamExecutionEnvironment
        from flink_tpu.windowing.assigners import TumblingEventTimeWindows

        def build():
            rng = np.random.default_rng(7)
            keys = np.concatenate([np.repeat(np.arange(5000), 2),
                                   rng.integers(0, 100, 50_000)])
            vals = np.ones(len(keys), np.float32)
            ts = np.full(len(keys), 100, np.int64)
            env = StreamExecutionEnvironment()
            env.set_parallelism(2)
            (env.from_collection(columns={"k": keys, "v": vals, "t": ts},
                                 batch_size=128)
                .assign_timestamps_and_watermarks(0, timestamp_column="t")
                .key_by("k")
                .window(TumblingEventTimeWindows.of(1000))
                .sum("v").collect())
            return env.get_stream_graph("incr-job")
    '''))
    sys.path.insert(0, str(tmp_path))
    try:
        storage = IncrementalCheckpointStorage(str(tmp_path / "ckpt"),
                                               retain=4,
                                               max_increments_per_base=4)
        pc = ProcessCluster("incr_job_mod:build", n_workers=2,
                            checkpoint_storage=storage,
                            checkpoint_interval_ms=30,
                            incremental=True,
                            extra_sys_path=(str(tmp_path),))
        res = pc.run(timeout_s=240)
        assert res["state"] == "FINISHED", res.get("error")
        incs = [s for s in pc._checkpoint_stats if s.get("incremental")]
        assert incs, pc._checkpoint_stats
        steady = incs[-1]
        assert steady["delta_bytes"] <= 0.25 * steady["state_size_bytes"]
        snap = storage.load_latest()
        assert snap is not None and not delta.tree_has_increment(snap)
        assert sum(r["v"] for r in res["rows"]) == 60_000
    finally:
        sys.path.remove(str(tmp_path))
        sys.modules.pop("incr_job_mod", None)
