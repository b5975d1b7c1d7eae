"""The harness's own checks, run by hand (they are not tier-1 tests):

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_harness.py -q

Everything runs on the CPU at 2^12 keys; `run_cell` is driven directly, past
the CLI's look for a chip.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

import jobs.keyed_window as job  # noqa: E402
from harness import compare, readers, runner, trace_reduce  # noqa: E402
from harness.generator import RunClock, Stream, TrafficSource  # noqa: E402
from reference.keyed_window import Reference  # noqa: E402

CELLS = ["tumbling-sum-1m.backlog", "sliding-multiagg-1m.backlog",
         "tumbling-sum-1m.paced"]


def small(cell_name: str) -> dict:
    """The cell at 2^12 keys: same shapes of traffic, a few thousand events
    a slide."""
    _, config, _ = runner.load_cell(cell_name)
    return {
        "config": {"keys": {"count": 4096}, "batch_events": 256,
                   "guarantees": dict(config["guarantees"],
                                      checkpoint_interval_ms=500)},
        "traffic": {"events_per_slide": 16384, "rate_events_per_s": 5120},
    }


def small_stream(cell_name: str, seed: int):
    """(stream, configuration) of the cell at the small size."""
    _, config, traffic = runner.load_cell(cell_name)
    over = small(cell_name)
    config.update(over["config"])
    traffic.update(over["traffic"])
    return Stream(config, traffic, seed), config


def run(cell_name: str, seed: int, seconds: float) -> dict:
    return runner.run_cell(cell_name, seed, seconds, False, time.monotonic(),
                           overrides=small(cell_name), say=lambda _m: None)


# -- the generator -----------------------------------------------------------

def test_batches_are_a_function_of_seed_and_index():
    a, _ = small_stream(CELLS[0], 2**31 + 7)
    b, _ = small_stream(CELLS[0], 2**31 + 7)
    c, _ = small_stream(CELLS[0], 2**31 + 8)
    assert np.array_equal(a.universe, b.universe)
    for index in (0, 5, a.warm_batches - 1, a.warm_batches + 3):
        for x, y in zip(a.columns(index), b.columns(index)):
            assert np.array_equal(x, y)
        assert not np.array_equal(a.columns(index)[1], c.columns(index)[1])
        assert not np.array_equal(a.columns(index)[1],
                                  a.columns(index + 1)[1])
    # the universe pass sends every key once; event time never runs back
    first = np.concatenate([a.columns(i)[0]
                            for i in range(a.n_keys // a.batch)])
    assert np.array_equal(np.sort(first), np.arange(a.n_keys))
    for split in range(a.splits):       # per source task, that is
        ts = np.concatenate([a.columns(i)[2] for i in
                             range(split, a.warm_batches + 8, a.splits)])
        assert (np.diff(ts) >= 0).all()


@pytest.mark.parametrize("key,value", [
    ("keys", {"distribution": "zipf"}),
    ("lateness", {"share": 0.05, "bound_ms": 2000}),
    ("burst", "square"), ("mode", "closed")])
def test_unknown_traffic_is_an_error(key, value):
    _, config, traffic = runner.load_cell(CELLS[0])
    traffic[key] = value
    with pytest.raises(ValueError):
        Stream(config, traffic, 1)


def test_paced_schedule_is_open_loop():
    """A consumer that blocks makes the generator late, not slow: the due
    times stay on the grid and the batches behind the stall go out at once."""
    stream, _ = small_stream(CELLS[2], 5)
    clock = RunClock(seconds=1.5, started=time.monotonic())
    stream.splits = 1                   # one consumer thread is enough here
    source = TrafficSource(stream, clock)
    it = source.read_split(0, 1)
    for _ in range(stream.warm_batches):
        next(it)
    threading.Timer(0.05, clock.open_window).start()
    stalled = False
    for _batch in it:
        if not stalled and len(source.log[0]) == 5:
            time.sleep(0.4)             # the job stops taking input
            stalled = True
    rows = source.log[0]
    interval = stream.batch / stream.rate
    due = np.array([r[1] for r in rows])
    assert np.allclose(np.diff(due), interval)          # never re-planned
    assert len(rows) == int((clock.seconds + 4 * interval) / interval)
    late = np.array([r[2] - r[1] for r in rows])
    assert late[:5].max() < 0.02 and late[5] > 0.3      # late, and recorded
    assert late[-1] < 0.02                              # caught up: sent at once


# -- the comparison and its control ------------------------------------------

@pytest.mark.parametrize("cell_name", CELLS[:2])
@pytest.mark.parametrize("mode,correct", [
    ("exact", True), ("bf16", False), ("replay", False), ("drop", False)])
def test_control_fails_the_comparison(cell_name, mode, correct):
    cell = runner.load_json("workloads", f"{cell_name}.json")
    stream, config = small_stream(cell_name, 11)
    fields = job.output_fields(config)
    sent = list(range(stream.warm_batches + 3 * stream.batches_per_slide))
    rows = compare.control_rows(stream, Reference(config), fields, sent, mode,
                                pick=stream.warm_batches + 7)
    result = compare.compare(stream, Reference(config), fields, sent, rows)
    numbers, ok = compare.verdict(result.numbers, cell["limits"])
    assert ok is correct, numbers


# -- whole runs, through execute_cluster -------------------------------------

@pytest.mark.parametrize("cell_name,seconds", [
    (CELLS[0], 2.0), (CELLS[1], 2.0), (CELLS[2], 7.0)])
def test_rows_equal_the_reference(cell_name, seconds):
    line = run(cell_name, 2**31 + 99, seconds)
    assert line["correct"], line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    cell = runner.load_json("workloads", f"{cell_name}.json")
    assert set(line["metrics"]) == set(cell["end_to_end"])
    assert list(line)[-1] == "compared"


@pytest.mark.parametrize("cell_name,panes", [(CELLS[0], 1), (CELLS[1], 12)])
def test_the_warm_up_cuts_meet_both_live_pane_counts(monkeypatch, cell_name,
                                                     panes):
    """A checkpoint reads the live panes with one program per count; the
    warm-up's first cut holds a window's panes and one more (one split has
    crossed into the next slide), its second a window's."""
    from flink_tpu.operators.window_agg import WindowAggOperator as Op

    real, seen = Op.snapshot_state, []

    def noting(self):
        snap = real(self)
        seen.append((id(self), len(snap["panes"])))
        return snap
    monkeypatch.setattr(Op, "snapshot_state", noting)
    assert run(cell_name, 2**31 + 17, 1.0)["correct"]
    by_op = {}
    for op, live in seen:
        by_op.setdefault(op, []).append(live)
    assert len(by_op) == 2
    for lives in by_op.values():
        assert lives[:2] == [panes + 1, panes], lives
        # the last is the final cut at the end of input, after the drain
        assert set(lives[:-1]) <= {panes, panes + 1}, lives


def test_a_traced_line_holds_the_cells_layer_metrics(monkeypatch):
    """The readers run on a whole run's counters; the device trace is the
    recorded one (the CPU has none)."""
    path = os.path.join(HERE, "data", "tumbling-sum-1m.backlog.xplane.pb")
    reduced = trace_reduce.reduce_planes(*trace_reduce.read_planes(path))
    monkeypatch.setattr(runner.jax.profiler, "start_trace",
                        lambda *a, **k: None)
    monkeypatch.setattr(runner.jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(trace_reduce, "reduce_dir", lambda _d: reduced)
    monkeypatch.setattr(readers, "load_peaks", lambda _k: {
        "hbm_bytes_per_s": 819e9, "flops_per_s": 197e12})
    over, said = small(CELLS[0]), []
    line = runner.run_cell(CELLS[0], 2**31 + 5, 2.0, True, time.monotonic(),
                           overrides=dict(over, cell={"trace_slice": {
                               "start_s": 0.3, "length_s": 1.0}}),
                           say=said.append)
    cell = runner.load_json("workloads", f"{CELLS[0]}.json")
    assert set(line["metrics"]) == set(cell["per_layer"])
    assert line["device"]["busy_s"] > 0
    per_second = [json.loads(m.split(": ", 1)[1]) for m in said
                  if m.startswith("handed per second")]
    assert len(per_second[0]) == 2 and sum(per_second[0]) \
        * over["config"]["batch_events"] == line["attempted"]
    reduced["modules"].pop("_update_step")
    with pytest.raises(RuntimeError, match="_update_step"):
        runner.run_cell(CELLS[0], 2**31 + 5, 2.0, True, time.monotonic(),
                        overrides=dict(over, cell={"trace_slice": {
                            "start_s": 0.3, "length_s": 1.0}}),
                        say=lambda _m: None)


def _every_nth(n, opened):
    """True on every n-th call once the measured window is open (a fault in
    the warm-up stops the run before there is a result to judge)."""
    calls = {"n": 0}

    def due():
        calls["n"] += bool(opened)
        return bool(opened) and calls["n"] % n == 0
    return due


def test_a_broken_timed_path_is_not_correct(monkeypatch):
    """Each fault a one-chip cell can have, planted under a whole run."""
    from flink_tpu.operators.window_agg import WindowAggOperator as Op

    opened, open_window = [], RunClock.open_window

    def open_and_note(self):
        open_window(self)
        opened.append(True)
    monkeypatch.setattr(RunClock, "open_window", open_and_note)

    def state_unchanged(monkeypatch):
        real, due = Op._guarded_update, _every_nth(40, opened)

        def fake(self, flat_p, values_p, mb):
            if due():
                return self._leaves, self._counts, self._counts
            return real(self, flat_p, values_p, mb)
        monkeypatch.setattr(Op, "_guarded_update", fake)

    def half_a_batch(monkeypatch):
        real, due = Op.process_batch, _every_nth(40, opened)

        def fake(self, batch):
            if due():
                batch = batch.take(np.arange(len(batch) // 2))
            return real(self, batch)
        monkeypatch.setattr(Op, "process_batch", fake)

    def answer_altered(monkeypatch):
        real, due = Op._rows_for_keys, _every_nth(3, opened)

        def fake(self, keys, result, window):
            if due() and len(keys):
                result = np.array(result, copy=True)
                result[0] *= 1.001
            return real(self, keys, result, window)
        monkeypatch.setattr(Op, "_rows_for_keys", fake)

    for plant in (state_unchanged, half_a_batch, answer_altered):
        del opened[:]
        with monkeypatch.context() as m:
            plant(m)
            line = run(CELLS[0], 2**31 + 3, 1.5)
        assert not line["correct"], (plant.__name__, line["compared"])


def test_a_job_off_the_device_tier_fails(monkeypatch):
    over = small(CELLS[0])
    over["config"]["agg_options"] = {"emit_tier": "host"}
    with pytest.raises(RuntimeError, match="emit tier"):
        runner.run_cell(CELLS[0], 1, 1.0, False, time.monotonic(),
                        overrides=over, say=lambda _m: None)


# -- the trace reduction -----------------------------------------------------

def test_reduce_planes_on_known_intervals():
    ms = 1_000_000
    planes = [
        ("/device:TPU:0", [
            ("XLA Ops", [("a", 0, 2 * ms), ("b", 1 * ms, 2 * ms),
                         ("a", 6 * ms, 1 * ms)]),
            ("XLA Modules", [("jit__update_step(123)", 0, 3 * ms),
                             ("jit__fire_gather_step(9)", 6 * ms, 1 * ms)])]),
        ("/host:CPU", [("t", [("bench.sink.write", 3 * ms, 2 * ms),
                              ("other", 0, 10 * ms)])]),
    ]
    out = trace_reduce.reduce_planes(planes, extent=(0, 10 * ms))
    assert out["busy_s"] == pytest.approx(0.004)        # [0,3) + [6,7)
    assert out["window_s"] == pytest.approx(0.010)
    assert out["modules"]["_update_step"] == {"seconds": 0.003, "runs": 1}
    assert out["breakdown"]["device_ops"][0] == ["a", 0.003]
    assert out["breakdown"]["idle_gaps"][:2] == [
        ["bench.sink.write", 0.003], ["host: no annotated span", 0.003]]


def test_reduce_the_recorded_chip_trace():
    """A slice cut from this PR's first traced chip run of
    tumbling-sum-1m.backlog; the expected numbers are in the file beside it,
    worked out by rasterising the device events onto a microsecond grid."""
    path = os.path.join(HERE, "data", "tumbling-sum-1m.backlog.xplane.pb")
    with open(os.path.join(HERE, "data", "expected.json")) as f:
        want = json.load(f)
    out = trace_reduce.reduce_planes(*trace_reduce.read_planes(path))
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-3)
    assert out["window_s"] == pytest.approx(want["window_s"], rel=1e-6)
    assert out["modules"]["_update_step"]["runs"] == want["update_step_runs"]


# -- BENCHMARK.json and the data files agree ----------------------------------

def test_benchmark_json_matches_the_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for cell in bench["workloads"]:
        spec, config, traffic = runner.load_cell(cell["name"])
        assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
        assert (spec["config"], spec["traffic"], spec["chips"]) == (
            cell["config"], cell["traffic"], cell["chips"])
        for name, unit in spec["end_to_end"].items():
            assert e2e[name]["unit"] == unit
            assert cell["name"] in e2e[name].get("workloads",
                                                 [cell["name"]])
    for config in bench["configs"]:
        with open(os.path.join(ROOT, config["file"])) as f:
            body = json.load(f)
        assert body["source"] == config["source"]
        assert body["reduced"] == config["reduced"]
    # per-layer metrics: BENCHMARK.json lists each with its cells, every
    # cell's file maps the names it reports to a reader's file, and the two
    # agree in both directions
    files = {fname[:-5]: runner.load_json("layer_metrics", fname)
             for fname in os.listdir(os.path.join(BENCH, "layer_metrics"))}
    reported = {}
    for cell in bench["workloads"]:
        spec = runner.load_json("workloads", f"{cell['name']}.json")
        for name, stem in spec["per_layer"].items():
            reported.setdefault(name, []).append((cell["name"], files[stem]))
    assert set(reported) == {m["name"] for m in bench["per_layer"]}
    assert {id(f) for uses in reported.values() for _, f in uses} \
        == {id(f) for f in files.values()}          # no file without a cell
    for metric in bench["per_layer"]:
        cells = [c for c, _ in reported[metric["name"]]]
        assert metric["workloads"] == cells
        moved = e2e[metric["moves"]]
        for cell_name, spec in reported[metric["name"]]:
            assert cell_name in moved.get("workloads", [cell_name])
            assert {k: metric[k] for k in
                    ("unit", "better", "source", "layer")} == \
                {k: spec[k] for k in ("unit", "better", "source", "layer")}


def test_cut_times_fall_whole_inside_the_window():
    assert runner.cut_times(10.0, 30.0) == [2.5, 12.5, 22.5]
    assert runner.cut_times(10.0, 51.0) == [2.5, 12.5, 22.5, 32.5, 42.5]
    assert runner.cut_times(0.5, 2.0) == [0.125, 0.625, 1.125, 1.625]
