"""BASELINE config 5 through the public path (ISSUE 35): a SQL group-window
aggregate (SUM, COUNT(*), MIN, MAX, AVG) planned by ``flink_tpu/sql`` on
``env.set_mesh(4)``, run on the MiniCluster on forced host devices at a small
size, against the cell's own plain reference
(``benchmarks/reference/sql_group_window.py``) on seeded data, judged by the
benchmark's own comparison (``benchmarks/harness/compare.py``) under the
cell's limits.  The job is ``benchmarks/jobs/sql_group_window.py``: the
statement of ``benchmarks/configs/sql-tumble-multiagg-1m-mesh4.json`` for
TUMBLE, and the same select list over HOP(5 s, 60 s)."""

import json
import os
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest
from ml_dtypes import bfloat16

from flink_tpu.connectors.sinks import CollectSink
from flink_tpu.connectors.sources import Source
from flink_tpu.core.batch import RecordBatch
from flink_tpu.datastream.api import StreamExecutionEnvironment
from flink_tpu.operators.window_agg import WindowAggOperator
from flink_tpu.parallel.mesh_runtime import MeshWindowAggOperator
from flink_tpu.runtime.checkpoint.storage import InMemoryCheckpointStorage
from flink_tpu.sql.table_env import TableEnvironment

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CELL = "sql-tumble-multiagg-1m-mesh4.backlog"
N_KEYS, BATCH, N_BATCHES, BATCH_MS, SLIDE_MS = 600, 500, 64, 1250, 5000
CUT_AT = (22, 45)           # batch indices before which a checkpoint is cut


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CONFIG = load("configs", "sql-tumble-multiagg-1m-mesh4.json")
LIMITS = load("workloads", f"{CELL}.json")["limits"]
TUMBLE = CONFIG["sql"]
#: the statement's select list over the other group window config 5 names
HOP = TUMBLE.replace("TUMBLE_START(ts, ", "HOP_START(ts, INTERVAL '5' SECOND, ") \
            .replace("TUMBLE_END(ts, ", "HOP_END(ts, INTERVAL '5' SECOND, ") \
            .replace("TUMBLE(ts, ", "HOP(ts, INTERVAL '5' SECOND, ") \
            .replace("INTERVAL '5' SECOND)", "INTERVAL '60' SECOND)")
JOBS = {
    "tumble": dict(CONFIG, keys={"count": N_KEYS}),
    "hop": dict(CONFIG, keys={"count": N_KEYS}, sql=HOP,
                assigner={"kind": "sliding", "size_ms": 60000,
                          "slide_ms": SLIDE_MS}),
}


@pytest.fixture(scope="module")
def bench():
    """The cell's job, reference and comparison, imported as the benchmark
    imports them (`benchmarks/` on `sys.path`), and taken off again."""
    import importlib

    sys.path.insert(0, BENCH)
    try:
        yield SimpleNamespace(
            job=importlib.import_module("jobs.sql_group_window"),
            reference=importlib.import_module("reference.sql_group_window"),
            compare=importlib.import_module("harness.compare"))
    finally:
        sys.path.remove(BENCH)
        for name in [n for n in sys.modules
                     if n.split(".")[0] in ("jobs", "reference", "harness")]:
            del sys.modules[name]


class SeededSource(Source):
    """`N_BATCHES` batches of `(seed, index)`, `BATCH_MS` of event time
    each.  With `env` it asks the running cluster for a checkpoint before
    the batches `CUT_AT`, from the source task's own thread, so both cuts
    fall mid-window whatever the machine's speed.  It yields batches and
    nothing else, so a restored task's replay skips to the same place."""

    def __init__(self, seed: int, env=None, values=lambda v: v):
        self.env = env
        self.universe = np.unique(np.random.default_rng([seed, 0]).integers(
            1, 1 << 62, 2 * N_KEYS, dtype=np.int64))[:N_KEYS]
        self.seed = seed
        self.values = values
        self.cuts = []

    def columns(self, b: int):
        rng = np.random.default_rng([self.seed, 1, b])
        kidx = rng.integers(0, N_KEYS, BATCH)
        ts = b * BATCH_MS + np.arange(BATCH, dtype=np.int64) * BATCH_MS // BATCH
        return kidx, rng.random(BATCH, dtype=np.float32), ts

    def read_split(self, index: int, of: int):
        for b in range(N_BATCHES):
            if self.env is not None and b in CUT_AT:
                asked = time.monotonic()
                # the earlier cut waits for no barrier of this task's
                while (cid := self.env.last_cluster.trigger_checkpoint()) \
                        is None:
                    assert time.monotonic() - asked < 60.0
                    time.sleep(0.002)
                self.cuts.append(cid)
            kidx, v, ts = self.columns(b)
            yield RecordBatch({"k": self.universe[kidx],
                               "v": self.values(v), "ts": ts})


def run(build, config, source, env, restore=None):
    """SimpleNamespace(rows, window operator, job result, tasks' status,
    storage) of one run of the statement on `env`."""
    sink, storage = CollectSink(), InMemoryCheckpointStorage(retain=10)
    build(env, source, sink, config)
    result = env.execute_cluster(
        "sql-mesh", storage=storage, restore=restore,
        checkpoint_interval_ms=0, channel_capacity=2, timeout_s=300.0)
    assert result.state == "FINISHED", result.error
    ops = [member for task in env.last_cluster.tasks()
           for member in getattr(getattr(task, "operator", None),
                                 "operators", [])
           if isinstance(member, WindowAggOperator)]
    assert len(ops) == 1
    return SimpleNamespace(rows=sink.rows(), op=ops[0], result=result,
                           status=env.last_cluster.job_status(),
                           storage=storage, source=source)


def unsharded(env, source, sink, config):
    """`jobs/sql_group_window.py`'s three calls without `env.set_mesh`."""
    tenv = TableEnvironment(parallelism=1)
    tenv.register_source("lineitem", source, ["k", "v", "ts"], rowtime="ts")
    tenv.sql_query(config["sql"]).to_data_stream(env).add_sink(sink)


def judge(bench, config, source, rows):
    """The benchmark's comparison of `rows` with the reference over every
    batch: ((numbers beside their limits, correct), the comparison)."""
    reference = bench.reference.Reference(config)
    for b in range(N_BATCHES):
        reference.add(*source.columns(b))
    size = config["assigner"]["size_ms"]
    stream = SimpleNamespace(universe=source.universe, n_keys=N_KEYS,
                             size_ms=size)
    delivered = [(0.0, {name: np.asarray([r[name] for r in rows])
                        for name in rows[0]})]
    groups = bench.compare.group_by_window(delivered)
    comparison = bench.compare.Comparison(
        stream, bench.job.output_fields(config))
    panes = reference.pane_ids()
    for pane in range(panes[0], panes[-1] + size // SLIDE_MS):
        end = (pane + 1) * SLIDE_MS
        comparison.window(end, reference.window(end), groups.pop(end, []))
    comparison.leftovers(groups)
    return bench.compare.verdict(comparison.numbers, LIMITS), comparison


@pytest.fixture(scope="module")
def ran(bench):
    """Per statement, the runs the cases share, each made once."""
    made = {}

    def get(window, which):
        if (window, which) in made:
            return made[window, which]
        config, seed = JOBS[window], 2**31 + 35
        if which == "mesh":         # four devices, two cuts mid-stream
            env = StreamExecutionEnvironment(parallelism=1)
            out = run(bench.job.build, config, SeededSource(seed, env), env)
        elif which == "restored":   # a new job from the mesh run's 2nd cut
            first = get(window, "mesh")
            out = run(bench.job.build, config, SeededSource(seed),
                      StreamExecutionEnvironment(parallelism=1),
                      restore=first.storage.load(first.source.cuts[1]))
        elif which == "one-device":
            out = run(unsharded, config, SeededSource(seed),
                      StreamExecutionEnvironment(parallelism=1))
        elif which == "bf16":       # values taken in the precision below
            lowered = lambda v: v.astype(bfloat16).astype(np.float32)  # noqa: E731
            out = run(unsharded, config, SeededSource(seed, values=lowered),
                      StreamExecutionEnvironment(parallelism=1))
        made[window, which] = out
        return out

    return get


def rows_equal_the_reference(bench, ran, window):
    """Rows exactly the touched (key, window) cells, counts and min/max
    exact, sums and AVG inside the cell's `sum_rel_gap`, through two
    completed checkpoints."""
    out = ran(window, "mesh")
    assert len(out.source.cuts) == 2
    assert set(out.source.cuts) <= set(out.result.completed_checkpoints)
    (numbers, correct), comparison = judge(
        bench, JOBS[window], out.source, out.rows)
    assert correct, numbers
    assert set(numbers) == set(LIMITS)
    assert 0 < numbers["sum_rel_gap"]["value"] < LIMITS["sum_rel_gap"]
    assert comparison.rows_compared == len(out.rows) > 10 * N_KEYS
    # the select list, and the row's timestamp the collecting sink adds
    assert set(out.rows[0]) - {"__ts__"} == {
        "k", "window_start", "window_end", "total", "n", "lo", "hi", "mean"}


def a_restored_job_delivers_the_rest(bench, ran, window):
    """A new job restored from the second cut (which fell mid-window)
    replays the source from the cut's offset only, and its sink, restored
    with the rows delivered before the cut, ends with every row of the
    stream: what the state held at the cut and what came after it, each
    record once."""
    first, out = ran(window, "mesh"), ran(window, "restored")
    assert (CUT_AT[1] * BATCH_MS) % SLIDE_MS            # mid-window
    snapshot = first.storage.load(first.source.cuts[1])
    offset, = [sub["source_offset"] for name, vertex in snapshot.items()
               if name != "__job__" for sub in vertex["subtasks"]
               if "source_offset" in sub]
    assert CUT_AT[1] <= offset <= CUT_AT[1] + 1
    assert out.op.phase_bytes["exchange_route_batches"] == N_BATCHES - offset
    (numbers, correct), comparison = judge(
        bench, JOBS[window], out.source, out.rows)
    assert correct, numbers
    assert comparison.rows_compared == len(out.rows) == len(first.rows)


def one_device_gives_the_same_rows(bench, ran, window):
    """The statement with no mesh (one device, the pane-major ring, the
    gather fire): the same cells, counts and min/max bit for bit, sums and
    AVG inside the cell's tolerance of each other."""
    sharded, single = ran(window, "mesh"), ran(window, "one-device")
    assert not isinstance(single.op, MeshWindowAggOperator)

    def cells(rows):
        out = {(int(r["k"]), int(r["window_end"])): r for r in rows}
        assert len(out) == len(rows)
        return out

    a, b = cells(sharded.rows), cells(single.rows)
    assert a.keys() == b.keys()
    for cell, got in a.items():
        want = b[cell]
        assert (got["n"], got["lo"], got["hi"], got["window_start"]) \
            == (want["n"], want["lo"], want["hi"], want["window_start"])
        for column in ("total", "mean"):
            assert abs(got[column] - want[column]) \
                <= LIMITS["sum_rel_gap"] * max(abs(want[column]), 1.0)


def the_tier_is_device_with_no_option(bench, ran, window):
    out = ran(window, "mesh")
    assert isinstance(out.op, MeshWindowAggOperator)
    assert out.op.emit_tier == "device"
    assert out.op.device_sync_mode == "scatter"
    stats = out.op.device_health_stats()
    assert not stats["degraded"] and not stats["quarantine_migrations"]
    assert out.op.fused_stats()["hot_dispatches"] >= N_BATCHES
    # seven state arrays, a quarter of the key rows on each of four devices
    arrays = (*out.op._leaves, out.op._counts)
    assert len(arrays) == 7
    for a in arrays:
        assert len(a.sharding.device_set) == 4


def the_plan_ships_five_value_leaves(bench, ran, window):
    """One `<alias>_in` column per aggregate call with an argument (SUM,
    MIN, MAX, AVG: four copies of `v`) and `__ones` for COUNT(*)."""
    out = ran(window, "mesh")
    counted = out.op.phase_bytes
    assert counted["exchange_route_batches"] >= N_BATCHES
    assert counted["exchange_value_leaves"] \
        == 5 * counted["exchange_route_batches"]
    # the chain counts what the planner's two maps projected, per task,
    # beside every other member of the plan's chains
    seen = {}
    for vertex in out.status["vertices"]:
        for subtask in vertex["subtasks"]:
            seen.update(subtask["chain_stats"])
    assert seen["sql.pre_project"]["rows"] == N_BATCHES * BATCH
    assert seen["sql.pre_project"]["batches"] == N_BATCHES
    assert seen["sql.project"]["rows"] == len(out.rows)
    assert seen["window_agg.process_batch"]["rows"] == N_BATCHES * BATCH
    assert seen["sink.invoke"]["rows"] == len(out.rows)
    assert all(s["ns"] >= s["cpu_ns"] > 0 for s in seen.values())


def bfloat16_values_fail_the_comparison(bench, ran, window):
    out = ran(window, "bf16")
    exact = SeededSource(out.source.seed)
    (numbers, correct), _ = judge(bench, JOBS[window], exact, out.rows)
    assert not correct
    assert numbers["count_mismatch"]["value"] == 0
    assert numbers["rows_missing"]["value"] == 0
    assert numbers["minmax_mismatch"]["value"] > 0
    assert numbers["sum_rel_gap"]["value"] > 10 * LIMITS["sum_rel_gap"]


CHECKS = (rows_equal_the_reference, a_restored_job_delivers_the_rest,
          one_device_gives_the_same_rows, the_tier_is_device_with_no_option,
          the_plan_ships_five_value_leaves,
          bfloat16_values_fail_the_comparison)


@pytest.mark.parametrize("check", CHECKS, ids=lambda f: f.__name__)
@pytest.mark.parametrize("window", sorted(JOBS))
def test_sql_group_window_on_a_mesh(bench, ran, window, check):
    check(bench, ran, window)
