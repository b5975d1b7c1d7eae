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

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_tpu.connectors.sinks import CollectSink
from flink_tpu.connectors.sources import Source
from flink_tpu.core.batch import RecordBatch, Watermark
from flink_tpu.core.functions import (CountAggregator, MaxAggregator,
                                      MinAggregator, RuntimeContext,
                                      SumAggregator, TupleAggregator)
from flink_tpu.datastream.api import StreamExecutionEnvironment
from flink_tpu.operators.window_agg import (WindowAggOperator,
                                            _snapshot_read_step)
from flink_tpu.parallel.mesh import make_mesh
from flink_tpu.parallel.mesh_runtime import MeshWindowAggOperator
from flink_tpu.runtime.checkpoint.storage import InMemoryCheckpointStorage
from flink_tpu.state.shard_layout import has_shard_slices
from flink_tpu.windowing.assigners import (SlidingEventTimeWindows,
                                           TumblingEventTimeWindows)

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


def run_job(job, env):
    """(job, source, rows, operator, job result) of one run on `env`."""
    source, sink = SeededSource(env, seed=2**31 + 28), CollectSink()
    bench_module("jobs", "keyed_window").build(env, source, sink, job)
    result = env.execute_cluster(
        "mesh-device-lane", storage=InMemoryCheckpointStorage(),
        checkpoint_interval_ms=0, channel_capacity=2, timeout_s=300.0)
    return job, source, sink.rows(), window_operator(env), result


@pytest.fixture(scope="module", params=sorted(JOBS))
def ran(request):
    """One run of the job per deployment on the four-device mesh."""
    return run_job(
        JOBS[request.param],
        StreamExecutionEnvironment(parallelism=1).set_mesh(n_devices=4))


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


def test_sharding_changes_no_bit_of_a_full_window(ran):
    """The same job with no mesh (one device, the pane-major ring, the
    gather fire) delivers the mesh's rows: counts, min and max bit for bit
    in every window, and so the f32 sums of every window whose panes are
    all in the ring, since the stable bucket plan keeps a cell's records
    in batch order through the exchange.  That is what lets the benchmark's
    `reference/keyed_window_mesh.py` be `keyed_window.py`'s class.  Where a
    window reaches past the ring's ends (the stream's first and last
    slides) the gather fire combines the retained panes only and the dense
    fire every pane, identity cells included: another order of the same
    additions, the last bits of the sum."""
    job, _source, rows, _op, _result = ran
    *_, plain_rows, plain_op, plain = run_job(
        job, StreamExecutionEnvironment(parallelism=1))
    assert plain.state == "FINISHED", plain.error
    assert not isinstance(plain_op, MeshWindowAggOperator)
    fields = bench_module("jobs", "keyed_window").output_fields(job)

    def cells(delivered):
        out = {(int(r["k"]), int(r["window_end"])):
               {f: np.float32(r[f]) for f in fields} for r in delivered}
        assert len(out) == len(delivered)
        return out

    sharded, single = cells(rows), cells(plain_rows)
    assert sharded.keys() == single.keys()
    size = job["assigner"]["size_ms"]
    last = max(end for _, end in sharded)
    full = 0
    for cell, got in sharded.items():
        want = single[cell]
        whole = size <= cell[1] <= last - size + SLIDE_MS
        full += whole
        for field, kind in fields.items():
            if whole or kind != "sum":
                assert got[field].tobytes() == want[field].tobytes(), \
                    (cell, field, got, want)
            else:
                assert abs(got[field] - want[field]) \
                    <= 4 * np.spacing(want[field]), (cell, field, got, want)
    assert full >= N_KEYS * (N_BATCHES // 4 - size // SLIDE_MS)


def test_what_the_deployment_adds_is_counted(ran):
    """`exchange_value_leaves`, `exchange_live` by the width a column has
    on the device, `fire_dense_cells` and `snapshot_column_reads`."""
    job, source, rows, op, _result = ran
    counted = op.phase_bytes
    # the tuple aggregate's selector hands the whole row (k, ts, v) to the
    # exchange; the sum its one value column
    leaves = 3 if job["aggregate"]["kind"] == "tuple" else 1
    batches = counted["exchange_route_batches"]
    assert batches >= N_BATCHES
    assert counted["exchange_value_leaves"] == leaves * batches
    # a live row: the int32 flat id and each leaf as `device_put` left it
    # (with x64 off the int64 `k` and `ts` are int32 there; `v` is f32)
    wide = jax.dtypes.canonicalize_dtype(np.int64).itemsize
    row = 4 + (wide + wide + 4 if leaves == 3 else 4)
    assert counted["exchange_live"] == N_BATCHES * BATCH * row
    # a dense fire combines every key row's panes of every state array
    arrays = len(op._leaves) + 1
    panes = job["assigner"]["size_ms"] // SLIDE_MS
    fires = len({int(r["window_end"]) for r in rows})
    assert counted["fire_dense_cells"] == fires * op._K * panes * arrays
    # a cut reads each live pane of each array as one column of 4-byte
    # cells, and keeps the rows of the keys seen (all of them by then); a
    # pane is live from its first record until the last window over it
    # has fired and its column was cleared
    reads, cuts = counted["snapshot_column_reads"], len(source.cuts)
    assert counted["d2h_snapshot"] == reads * N_KEYS * 4
    assert reads % arrays == 0
    assert cuts * panes <= reads // arrays <= cuts * (panes + 1)
    assert counted["d2h_fire"] > 0


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


def test_one_program_each_for_a_steady_sliding_run():
    """The sliding sum/count/min/max job at one geometry: the sharded update
    step (five state arrays, three scatter kinds) and the dense fire step
    (every window's pane count, the ring filling up included) compile once,
    a cut's column read once per dtype, and nothing after the first fire
    and cut, whatever the number of live panes."""
    field = lambda agg: ("v", agg)  # noqa: E731
    op = MeshWindowAggOperator(
        SlidingEventTimeWindows.of(4 * SLIDE_MS, SLIDE_MS),
        TupleAggregator({"total": field(SumAggregator(jnp.float32)),
                         "n": field(CountAggregator()),
                         "lo": field(MinAggregator(jnp.float32)),
                         "hi": field(MaxAggregator(jnp.float32))}),
        key_column="k", value_selector=lambda c: c, mesh=make_mesh(4),
        emit_tier="device", initial_key_capacity=1024)
    if op.mesh_step_cache_size() < 0:
        pytest.skip("jax build without the jit cache probe")
    op.open(RuntimeContext())
    sizes = lambda: (op.mesh_step_cache_size(),  # noqa: E731
                     WindowAggOperator._fire_step._cache_size(),
                     _snapshot_read_step._cache_size())
    before = sizes()
    rng = np.random.default_rng(32)
    keys = np.arange(BATCH, dtype=np.int64) * 7919 + 1
    rows, cuts, warm = 0, 0, None
    for b in range(40):
        ts = np.full(BATCH, b * 250, np.int64)
        op.process_batch(RecordBatch(
            {"k": keys[rng.permutation(BATCH)] if b else keys,
             "v": rng.random(BATCH).astype(np.float32)}, timestamps=ts))
        for out in op.process_watermark(Watermark(b * 250 + 249)):
            rows += len(out)
        if b % 6 == 5:                  # 2, 3, 4, 3, 4, 3 live panes
            assert has_shard_slices(op.snapshot_state())
            cuts += 1
            warm = warm or sizes()      # one fire and one cut behind us
    assert rows == 10 * BATCH and cuts == 6
    assert sizes() == warm
    step, fire, read = (a - b for a, b in zip(warm, before))
    assert (step, fire) == (1, 1) and read <= 2
    assert op.phase_bytes["snapshot_column_reads"] \
        == (2 + 3 + 4 + 3 + 4 + 3) * 5
    assert op.phase_bytes["fire_dense_cells"] == 10 * 1024 * 4 * 5
