"""One run of one cell: the job on the MiniCluster, the measured window, the
comparison, the metrics.  Everything here runs the same on any backend; the
look for a chip is `run.py`'s, so the tests can drive a whole run on the CPU
at a small size."""

from __future__ import annotations

import importlib
import json
import os
import shutil
import tempfile
import threading
import time

import jax

from . import compare as cmp_mod
from . import readers, trace_reduce
from .generator import RunClock, Stream, TrafficSource
from .sink import StampSink

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_cell(name: str):
    """(cell, configuration, traffic) by the cell's name."""
    cell = load_json("workloads", f"{name}.json")
    return (cell, load_json("configs", f"{cell['config']}.json"),
            load_json("traffic", f"{cell['traffic']}.json"))


def layer_metrics(cell: dict):
    """[(name as reported, reader's file)] of the cell's per-layer metrics.
    The cell's file maps each name it reports to a file of `layer_metrics/`
    (reader, parameters, unit, layer), so one file serves every cell and a
    new cell brings no copy of it."""
    return [(name, load_json("layer_metrics", f"{stem}.json"))
            for name, stem in cell["per_layer"].items()]


def window_operators(operators):
    """The WindowAggOperator instances among (possibly chained) operators."""
    from flink_tpu.operators.window_agg import WindowAggOperator

    found = []
    for op in operators:
        for member in getattr(op, "operators", [op]):
            if isinstance(member, WindowAggOperator):
                found.append(member)
    return found


def check_healthy(ops) -> None:
    """A job that finished off the device tier is not this benchmark's
    result."""
    from flink_tpu.runtime import device_health

    for op in ops:
        stats = op.device_health_stats()
        if stats["degraded"] or stats["quarantine_migrations"]:
            raise RuntimeError(f"operator left the device tier: {stats}")
        if op.emit_tier != "device":
            raise RuntimeError(f"emit tier resolved to {op.emit_tier!r}")
        if op.fused_stats()["hot_dispatches"] < 2:
            raise RuntimeError("a window task never dispatched to the device")
    status = device_health.status_snapshot()
    if status["state"] != "healthy" or status["quarantines"]:
        raise RuntimeError(f"device monitor quarantined: {status}")


class CompileCounter:
    """Counts programs compiled or loaded from the cache, and cache misses,
    through `jax.monitoring`; `mark()` starts the in-window count."""

    BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.programs = 0
        self.misses = 0
        self.names = []
        self._marks = {}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, _secs, **kw):
        if event == self.BACKEND_COMPILE:
            self.programs += 1
            self.names.append(kw.get("fun_name", "?"))

    def _event(self, event, **_kw):
        if event == self.CACHE_MISS:
            self.misses += 1

    def mark(self, name):
        self._marks[name] = (self.programs, self.misses)

    def between(self, a, b):
        (p0, m0), (p1, m1) = self._marks[a], self._marks[b]
        return {"programs": p1 - p0, "cache_misses": m1 - m0,
                "names": self.names[p0:p1]}


def program_tasks(env):
    """The running job's tasks.  The program has no public way to them:
    `execute_cluster` leaves the cluster on `env._last_cluster` (as
    `flink_tpu/__main__.py` reads it) and the cluster keeps its tasks in
    `_tasks`.  This is the one place the harness reaches past the public
    surface; everything it reads from a task or an operator is a public
    attribute (PERF.md, for the `tracing` issue)."""
    return list(env._last_cluster._tasks)


def snapshot_counters(tasks) -> dict:
    """The program's host counters, read without stopping anything."""
    from flink_tpu.cluster.task import SourceSubtask

    snap = {"source": [], "window": [], "phase_ns": [], "phase_bytes": []}
    for task in tasks:
        row = {k: getattr(task, k) for k in
               ("busy_ns", "idle_ns", "backpressure_ns", "records_in",
                "records_out")}
        if isinstance(task, SourceSubtask):
            snap["source"].append(row)
            continue
        ops = window_operators([task.operator])
        if ops:
            snap["window"].append(row)
            snap["phase_ns"].append(dict(ops[0].phase_ns))
            snap["phase_bytes"].append(dict(ops[0].phase_bytes))
    return snap


def _lanes(op) -> dict:
    return {"emit_tier": op.emit_tier, "device_sync_mode": op.device_sync_mode,
            "device_probe": op.device_probe_stats(),
            "fused": op.fused_stats(), "health": op.device_health_stats()}


def cut_times(interval_s: float, seconds: float):
    """Seconds after `t0` at which the window's checkpoints are asked for:
    one per interval, the first a quarter of an interval in, none in the
    window's last quarter interval, so that every cut's cost falls whole
    inside the window whatever the set-up took."""
    times, due = [], interval_s / 4
    while due <= seconds - interval_s / 4:
        times.append(due)
        due += interval_s
    return times


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             started: float, cache_dir: str = "", overrides=None,
             say=print) -> dict:
    """Run the cell once and return the result line as a dict.  `overrides`
    ({"config": {...}, "traffic": {...}, "cell": {...}}, shallow) is for the small-size
    rehearsal in the tests; the CLI never passes it."""
    from flink_tpu.datastream.api import StreamExecutionEnvironment
    from flink_tpu.runtime.checkpoint.storage import InMemoryCheckpointStorage

    cell, config, traffic = load_cell(cell_name)
    for part, target in (("config", config), ("traffic", traffic),
                         ("cell", cell)):
        target.update((overrides or {}).get(part, {}))
    job = importlib.import_module(f"jobs.{config['job']}")
    reference_mod = importlib.import_module(f"reference.{config['job']}")

    counter = CompileCounter()
    entries_before = cache_entries(cache_dir)
    stream = Stream(config, traffic, seed)
    clock = RunClock(seconds, started)
    source = TrafficSource(stream, clock)
    sink = StampSink(stream.base_ms, source.sync_rows, clock.open_window)
    env = StreamExecutionEnvironment(parallelism=config["parallelism"])
    job.build(env, source, sink, config)

    snaps, traced = {}, {}
    interval_s = config["guarantees"]["checkpoint_interval_ms"] / 1000.0
    cuts = {"warm": [], "window": []}   # (id, seconds after t0 or None)

    def at_open():
        counter.mark("t0")
        snaps["t0"] = snapshot_counters(program_tasks(env))

    clock.at_open.append(at_open)

    def request_cut():
        # on a source task's thread, which cannot wait for its own barrier:
        # the cut is only asked for here
        cid = env._last_cluster.trigger_checkpoint()
        if cid is not None:
            cuts["warm"].append(cid)
        return cid

    clock.request_cut = request_cut

    def sleep_until(t):
        time.sleep(max(0.0, t - time.monotonic()))

    def checkpoints():
        """The configuration's checkpoints, one per interval, on a schedule
        anchored at `t0` (`cut_times`).  The cluster's own timer is anchored
        at the job's start, whose distance from `t0` is the set-up: with it
        a cut's cost (0.1 to 0.9 s of each window task) fell inside or
        outside the window's end as the set-up took longer or shorter.  The
        call is the one the cluster's timer makes."""
        clock.wait_open()
        for due in cut_times(interval_s, seconds):
            sleep_until(clock.t0 + due)
            cid = env._last_cluster.trigger_checkpoint()
            cuts["window"].append((cid, time.monotonic() - clock.t0))

    def monitor():
        clock.wait_open()
        if trace:
            slice_ = cell["trace_slice"]
            sleep_until(clock.t0 + slice_["start_s"])
            traced["dir"] = tempfile.mkdtemp(prefix="bench-trace-")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0     # runtime and annotations only
            jax.profiler.start_trace(traced["dir"], profiler_options=options)
            snaps["trace0"] = snapshot_counters(program_tasks(env))
            time.sleep(slice_["length_s"])
            snaps["trace1"] = snapshot_counters(program_tasks(env))
            jax.profiler.stop_trace()
        sleep_until(clock.t_end)
        counter.mark("t_end")
        snaps["t_end"] = snapshot_counters(program_tasks(env))

    threads = [threading.Thread(target=fn, name=f"bench-{fn.__name__}",
                                daemon=True) for fn in (monitor, checkpoints)]
    for thread in threads:
        thread.start()
    result = env.execute_cluster(
        f"bench-{cell_name}", storage=InMemoryCheckpointStorage(),
        checkpoint_interval_ms=0,       # asked for by `checkpoints`, above
        channel_capacity=config["channel_capacity"], timeout_s=1200.0)
    t_done = time.monotonic()
    if result.state != "FINISHED":
        raise RuntimeError(f"job {result.state}: {result.error}")
    for thread in threads:
        thread.join(timeout=60.0)
    if "t_end" not in snaps:
        raise RuntimeError("the job ended before the measured window did")
    asked = cuts["warm"] + [cid for cid, _ in cuts["window"]]
    if len(cuts["warm"]) != 2 or not cuts["window"] \
            or set(asked) - set(result.completed_checkpoints):
        raise RuntimeError(
            f"checkpoints asked for {cuts}, completed "
            f"{list(result.completed_checkpoints)}: the configuration's "
            f"guarantee was not kept")

    ops = window_operators(t.operator for t in program_tasks(env))
    if len(ops) != config["parallelism"]:
        raise RuntimeError(f"found {len(ops)} window subtasks")
    check_healthy(ops)
    device = jax.devices()[0]
    stats = device.memory_stats() or {}
    in_window = counter.between("t0", "t_end")
    say(f"window: t0 {clock.t0 - started:.3f} s after start, "
        f"{seconds} s, drain {t_done - clock.t_end:.3f} s; checkpoints "
        f"completed {list(result.completed_checkpoints)}, asked for in the "
        f"warm-up {cuts['warm']}, in the window (id, s after t0) "
        f"{[(c, round(t, 3)) for c, t in cuts['window']]}")
    say(f"compile cache: {cache_dir} ({entries_before} -> "
        f"{cache_entries(cache_dir)} entries); programs compiled or loaded "
        f"inside the window: {in_window}")
    for i, op in enumerate(ops):
        say(f"window operator {i}: lanes {json.dumps(_lanes(op))}")
        say(f"window operator {i}: phase_bytes "
            f"{json.dumps({k: int(v) for k, v in op.phase_bytes.items()})}")
    say(f"device memory_stats: {json.dumps(stats)}")

    # -- the comparison: after the window, the peak read, the job gone ------
    delivered, sent = sink.batches, source.sent_batches()
    fields = job.output_fields(config)
    t_cmp = time.monotonic()
    comparison = cmp_mod.compare(stream, reference_mod.Reference(config),
                                 fields, sent, delivered)
    numbers, correct = cmp_mod.verdict(comparison.numbers, cell["limits"])
    say(f"comparison: {comparison.rows_compared} rows of "
        f"{comparison.windows_compared} windows against the reference in "
        f"{time.monotonic() - t_cmp:.1f} s")

    ctx = readers.Context(config=config, stream=stream, clock=clock,
                          source=source, delivered=delivered, snaps=snaps,
                          cuts_in_window=len(cuts["window"]),
                          device_kind=device.device_kind)
    attempted, late = ctx.attempted_and_late()
    if stream.mode == "backlog":
        say("handed per second of the window, batches: "
            + json.dumps(ctx.handed_per_second()))
    if stream.mode == "paced":
        say("generator: sent late by p50/p95/max ms "
            + "/".join(f"{readers.generator_late_percentile(ctx, p):.3f}"
                       for p in (50, 95, 100))
            + f"; {late} events due and not taken in at the close")
        say("result latency p50/p95/max ms "
            + "/".join(f"{readers.result_latency_percentile(ctx, p):.3f}"
                       for p in (50, 95, 100)))
    failed = min(attempted, late + comparison.events_of_lost_windows)
    out_device = {"platform": device.platform, "kind": device.device_kind,
                  "count": len(jax.devices()),
                  "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": {}, "device": out_device}
    if trace:
        try:
            ctx.trace = trace_reduce.reduce_dir(traced["dir"])
        finally:
            shutil.rmtree(traced["dir"], ignore_errors=True)
        say("traced modules (device seconds, runs): " + json.dumps(
            {k: [round(v["seconds"], 6), v["runs"]]
             for k, v in sorted(ctx.trace["modules"].items())}))
        out_device["busy_s"] = ctx.trace["busy_s"]
        out_device["window_s"] = ctx.trace["window_s"]
        for name, spec in layer_metrics(cell):
            value = getattr(readers, spec["reader"])(ctx, **spec["params"])
            if value is not None:
                line["metrics"][name] = {"value": value, "unit": spec["unit"]}
        line["breakdown"] = ctx.trace["breakdown"]
    else:
        for name, (value, unit) in ctx.end_to_end(cell).items():
            line["metrics"][name] = {"value": value, "unit": unit}
    line["compared"] = numbers
    return line
