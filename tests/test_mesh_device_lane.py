"""The mesh job's device-current lane through the public API (ISSUE 28):
``env.set_mesh(4)`` -> ``key_by`` -> ``window`` -> ``aggregate(emit_tier=
"device")`` -> ``execute_cluster``, on a forced 4-device host mesh at small
sizes, against the benchmark's plain numpy reference
(``benchmarks/reference/keyed_window.py``) on seeded data, with two
checkpoints cut mid-stream.  Every batch rides the ``all_to_all`` exchange
into the sharded state; fires and cuts read it back from four devices."""

import importlib.util
import os
import time

import jax.numpy as jnp
import numpy as np
import pytest

from flink_tpu.connectors.sinks import CollectSink
from flink_tpu.connectors.sources import Source
from flink_tpu.core.batch import RecordBatch, Watermark
from flink_tpu.core.functions import RuntimeContext, SumAggregator
from flink_tpu.datastream.api import StreamExecutionEnvironment
from flink_tpu.operators.window_agg import WindowAggOperator
from flink_tpu.parallel.mesh import make_mesh
from flink_tpu.parallel.mesh_runtime import MeshWindowAggOperator
from flink_tpu.runtime.checkpoint.storage import InMemoryCheckpointStorage
from flink_tpu.state.shard_layout import has_shard_slices
from flink_tpu.windowing.assigners import TumblingEventTimeWindows

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_KEYS, BATCH, N_BATCHES, SLIDE_MS = 600, 500, 40, 1000
CUT_AT = (13, 27)           # batch indices before which a checkpoint is cut
SUM_REL_GAP = 2e-5          # the benchmark cells' limit

_OPTIONS = {"agg_options": {"emit_tier": "device"},
            "guarantees": {"watermark_out_of_orderness_ms": 0},
            "keys": {"count": N_KEYS}}
#: configurations in the form of `benchmarks/configs/*.json`, at a small size
JOBS = {
    "tumbling-sum": dict(
        _OPTIONS, assigner={"kind": "tumbling", "size_ms": SLIDE_MS},
        aggregate={"kind": "sum"}),
    "sliding-multiagg": dict(
        _OPTIONS,
        assigner={"kind": "sliding", "size_ms": 4 * SLIDE_MS,
                  "slide_ms": SLIDE_MS},
        aggregate={"kind": "tuple",
                   "fields": {"total": "sum", "n": "count", "lo": "min",
                              "hi": "max"}}),
}


def bench_module(*parts):
    """A module of `benchmarks/`, loaded by path (`benchmarks/` stays off
    `sys.path`): the job the cells run, built through the public API, and
    the plain numpy reference, which imports nothing of the program."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + "_".join(parts), os.path.join(ROOT, "benchmarks", *parts)
        + ".py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class SeededSource(Source):
    """`N_BATCHES` batches of `(seed, index)`; asks the running cluster for a
    checkpoint before the batches `CUT_AT`, from the source task's own
    thread (as the benchmark's generator does), so both cuts fall
    mid-stream whatever the machine's speed."""

    _TICK = Watermark(-(1 << 62))

    def __init__(self, env, seed: int):
        self.env = env
        self.universe = np.unique(np.random.default_rng([seed, 0]).integers(
            1, 1 << 62, 2 * N_KEYS, dtype=np.int64))[:N_KEYS]
        self.seed = seed
        self.cuts = []

    def columns(self, b: int):
        rng = np.random.default_rng([self.seed, 1, b])
        kidx = rng.integers(0, N_KEYS, BATCH)
        ts = b * 250 + np.arange(BATCH, dtype=np.int64) * 250 // BATCH
        return kidx, rng.random(BATCH, dtype=np.float32), ts

    def read_split(self, index: int, of: int):
        for b in range(N_BATCHES):
            if b in CUT_AT:
                asked = time.monotonic()
                while (cid := self.env.last_cluster.trigger_checkpoint()) \
                        is None:
                    assert time.monotonic() - asked < 60.0
                    yield self._TICK
                    time.sleep(0.002)
                self.cuts.append(cid)
            kidx, v, ts = self.columns(b)
            yield RecordBatch({"k": self.universe[kidx], "v": v, "ts": ts})


def window_operator(env):
    found = [member for task in env.last_cluster.tasks()
             for member in getattr(getattr(task, "operator", None),
                                   "operators", [])
             if isinstance(member, WindowAggOperator)]
    assert len(found) == 1
    return found[0]


@pytest.fixture(scope="module", params=sorted(JOBS))
def ran(request):
    """One run of the job per deployment: (job, source, rows, operator,
    job result)."""
    job = JOBS[request.param]
    env = StreamExecutionEnvironment(parallelism=1).set_mesh(n_devices=4)
    source, sink = SeededSource(env, seed=2**31 + 28), CollectSink()
    bench_module("jobs", "keyed_window").build(env, source, sink, job)
    result = env.execute_cluster(
        "mesh-device-lane", storage=InMemoryCheckpointStorage(),
        checkpoint_interval_ms=0, channel_capacity=2, timeout_s=300.0)
    return job, source, sink.rows(), window_operator(env), result


def test_rows_match_the_reference_exactly_once(ran):
    job, source, rows, _op, result = ran
    assert result.state == "FINISHED", result.error
    # both checkpoints were cut mid-stream and completed
    assert len(source.cuts) == 2
    assert set(source.cuts) <= set(result.completed_checkpoints)

    reference = bench_module("reference", "keyed_window").Reference(job)
    for b in range(N_BATCHES):
        reference.add(*source.columns(b))
    fields = bench_module("jobs", "keyed_window").output_fields(job)
    got = {}
    for r in rows:
        cell = (int(r["k"]), int(r["window_end"]))
        assert cell not in got, f"row {cell} delivered twice"
        got[cell] = r
    panes = job["assigner"]["size_ms"] // SLIDE_MS
    ends = [(p + 1) * SLIDE_MS for p in
            range(min(reference.pane_ids()),
                  max(reference.pane_ids()) + panes)]
    expected = 0
    for end in ends:
        want = reference.window(end)
        live = np.flatnonzero(want["count"])
        expected += live.size
        for i in live.tolist():
            row = got[(int(source.universe[i]), end)]
            for column, kind in fields.items():
                value, ref = row[column], want[kind][i]
                if kind == "sum":
                    assert abs(value - ref) / max(abs(ref), 1.0) \
                        < SUM_REL_GAP, (column, value, ref)
                else:       # counts, and f32 min / max: exact
                    assert value == ref, (column, value, ref)
    assert expected == len(got) > N_KEYS * (N_BATCHES // 4 - 1)


def test_the_lane_is_device_current_and_sharded(ran):
    _job, _source, _rows, op, _result = ran
    assert isinstance(op, MeshWindowAggOperator)
    assert op.emit_tier == "device" and op.device_sync_mode == "scatter"
    stats = op.device_health_stats()
    assert not stats["degraded"] and not stats["quarantine_migrations"]
    assert op.fused_stats()["hot_dispatches"] >= N_BATCHES
    for a in (*op._leaves, op._counts):
        assert len(a.sharding.device_set) == 4
        assert {s.data.shape[0] for s in a.addressable_shards} \
            == {a.shape[0] // 4}


def test_the_lane_is_timed_and_counted(ran):
    """The spans and counters the benchmark's per-layer metrics read."""
    _job, _source, _rows, op, _result = ran
    for phase in ("exchange_route", "device_dispatch", "fire", "fire_d2h",
                  "fire_assemble", "snapshot"):
        assert op.phase_ns.get(phase, 0) > 0, phase
    # the route is a part of the dispatch, the d2h and assembly of the fire
    assert op.phase_ns["exchange_route"] <= op.phase_ns["device_dispatch"]
    assert op.phase_ns["fire_d2h"] + op.phase_ns["fire_assemble"] \
        <= op.phase_ns["fire"]
    sent, live = (op.phase_bytes[k] for k in ("exchange_sent",
                                              "exchange_live"))
    # every record crossed the exchange once: its flat id (slot and pane
    # in one int32) and its value columns (the tuple aggregate ships the
    # whole row)
    assert live % (N_BATCHES * BATCH) == 0
    assert sent >= live >= N_BATCHES * BATCH * 8
    # how the routing went: a staged power of two splits over four chips
    # uncopied, and a capacity count is taken or skipped, never both
    routed, copied, skipped = (op.phase_bytes[k] for k in (
        "exchange_route_batches", "exchange_route_copied",
        "exchange_cap_counts_skipped"))
    assert routed >= N_BATCHES and copied == 0 and 0 <= skipped < routed


def windowed_on_a_mesh():
    env = StreamExecutionEnvironment().set_mesh(n_devices=4)
    return (env.from_collection(
                columns={"k": np.arange(8, dtype=np.int64),
                         "v": np.ones(8, np.float32)}, batch_size=8)
            .assign_timestamps_and_watermarks(0, timestamp_column="k")
            .key_by("k").window(TumblingEventTimeWindows.of(1000)))


@pytest.mark.parametrize("emit_tier,resolved", [
    ("device", "device"), (None, "device"), ("host", "host")])
def test_emit_tier_is_accepted_with_a_mesh(emit_tier, resolved):
    """`aggregate(emit_tier=...)` reaches the mesh operator; left out, a
    mesh job resolves to the device tier (on every backend), so `set_mesh`
    alone never leaves the chips untouched."""
    options = {} if emit_tier is None else {"emit_tier": emit_tier}
    stream = windowed_on_a_mesh().aggregate(
        SumAggregator(jnp.float32), value_column="v", **options)
    op = stream.transformation.operator_factory()
    assert isinstance(op, MeshWindowAggOperator)
    assert op.emit_tier == resolved


def test_paging_is_still_refused_with_a_mesh():
    from flink_tpu.state.paging import PagingConfig

    with pytest.raises(ValueError, match="paging"):
        windowed_on_a_mesh().aggregate(
            SumAggregator(jnp.float32), value_column="v",
            paging=PagingConfig(capacity=4))


def test_one_program_for_a_steady_run_with_fires_and_cuts():
    """At one geometry the sharded update step compiles once, fires, pane
    clears and snapshots included: none of them hands the state back in
    another sharding."""
    op = MeshWindowAggOperator(
        TumblingEventTimeWindows.of(SLIDE_MS), SumAggregator(jnp.float32),
        key_column="k", value_column="v", mesh=make_mesh(4),
        emit_tier="device", initial_key_capacity=1024)
    if op.mesh_step_cache_size() < 0:
        pytest.skip("jax build without the jit cache probe")
    op.open(RuntimeContext())
    before = op.mesh_step_cache_size()
    rng = np.random.default_rng(28)
    keys = np.arange(BATCH, dtype=np.int64) * 7919 + 1
    rows = 0
    for b in range(12):
        ts = np.full(BATCH, b * 250, np.int64)
        op.process_batch(RecordBatch(
            {"k": keys[rng.permutation(BATCH)] if b else keys,
             "v": rng.random(BATCH).astype(np.float32)}, timestamps=ts))
        for out in op.process_watermark(Watermark(b * 250 + 249)):
            rows += len(out)
        if b in (5, 9):
            assert has_shard_slices(op.snapshot_state())
    assert rows == 3 * BATCH            # three windows fired, every key each
    assert op.mesh_step_cache_size() == before + 1
