"""Checks of the span reduction and its readers, run by hand beside
`test_harness.py` (not tier-1 tests):

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_spans.py -q
"""

from __future__ import annotations

import json
import os
import sys
import time
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [HERE, BENCH, ROOT]

import spans as spans_cli  # noqa: E402
from test_harness import small  # noqa: E402  (the cells at 2^12 keys)
from harness import (readers, runner, span_readers, span_reduce,  # noqa: E402
                     trace_reduce)

MS = 1_000_000
OLD_FIXTURE = os.path.join(HERE, "data", "tumbling-sum-1m.backlog.xplane.pb")
FIXTURE = os.path.join(HERE, "data", "tumbling-sum-4k.paced.spans.xplane.pb")


def known_planes():
    """The device busy over [0,2) and [6,7) of a 10 ms slice, so idle over
    [2,6) and [7,10): 7 ms.  Thread a is in a batch whose dispatch reaches
    into the first gap; thread b in a snapshot (parent, phase, leaf) that
    spans both gaps; after 9 ms nothing is open anywhere."""
    return [
        ("/device:TPU:0", [
            ("XLA Ops", [("x", 0, 2 * MS), ("y", 6 * MS, 1 * MS)]),
            ("XLA Modules", [("jit__update_step(1)", 0, 2 * MS),
                             ("jit__snapshot_read_step(2)", 6 * MS, MS)])]),
        ("/host:CPU", [
            ("a", [("source.next", -1 * MS, int(1.5 * MS)),
                   ("task.process_batch", 1 * MS, int(4.5 * MS)),
                   ("window_agg.device_step", int(1.5 * MS), 3 * MS),
                   ("a runtime event of no interest", 0, 10 * MS)]),
            ("b", [("checkpoint.snapshot", 3 * MS, 6 * MS),
                   ("window_agg.snapshot", int(3.5 * MS), 5 * MS),
                   ("window_agg.snapshot_d2h", 4 * MS, 4 * MS),
                   ("window_agg.snapshot_d2h", 20 * MS, 1 * MS)])]),
    ]


def test_innermost_is_the_span_opened_last():
    events = known_planes()[1][1][1][1][:3]
    assert span_reduce.innermost(events) == [
        ("checkpoint.snapshot", 3 * MS, int(3.5 * MS)),
        ("window_agg.snapshot", int(3.5 * MS), 4 * MS),
        ("window_agg.snapshot_d2h", 4 * MS, 8 * MS),
        ("window_agg.snapshot", 8 * MS, int(8.5 * MS)),
        ("checkpoint.snapshot", int(8.5 * MS), 9 * MS)]
    # a child that overruns its parent by a clock's grain ends with it
    assert span_reduce.innermost([("p", 0, 10), ("c", 5, 7)]) == [
        ("p", 0, 5), ("c", 5, 10)]


def test_spans_and_idle_time_on_known_intervals():
    out = span_reduce.reduce_spans(known_planes(), extent=(0, 10 * MS))
    assert out["idle_s"] == pytest.approx(0.007)
    got = {name: (round(row["seconds"] * 1e3, 6), row["count"],
                  round(row["idle_s"] * 1e3, 6))
           for name, row in out["spans"].items()}
    assert got == {
        # began before the slice: its time inside counts, the event not
        "source.next": (0.5, 0, 0.0),
        # [4.5,5.5) of the first gap is the batch's own, after its dispatch
        "task.process_batch": (4.5, 1, 1.0),
        "window_agg.device_step": (3.0, 1, 2.5),          # [2,4.5)
        "checkpoint.snapshot": (6.0, 1, 1.0),             # [3,3.5) [8.5,9)
        "window_agg.snapshot": (5.0, 1, 1.0),             # [3.5,4) [8,8.5)
        # [4,6) [7,8); the second event lies outside the slice
        "window_agg.snapshot_d2h": (4.0, 1, 3.0),
    }
    # [9,10): idle with nothing open on either thread
    assert out["idle_unattributed_s"] == pytest.approx(0.001)
    family = ["checkpoint.snapshot", "window_agg.snapshot",
              "window_agg.snapshot_d2h", "window_agg.snapshot_assemble"]
    assert span_reduce.idle_under(out, family) == pytest.approx(0.005)
    # two threads inside listed spans at once count once: [2,6) [7,8), not
    # 2.5 + 3
    assert span_reduce.idle_under(out, ["window_agg.device_step",
                                        "window_agg.snapshot_d2h"]) \
        == pytest.approx(0.005)
    assert span_reduce.idle_under(out, ["sink.invoke"]) == 0.0


def context(trace, records=2_000_000):
    """As much of a `readers.Context` as the span readers touch."""
    ctx = types.SimpleNamespace(trace=trace, config={"parallelism": 2})
    ctx.delta = lambda role, key, span: {
        ("source", "records_in", ("trace0", "trace1")): records}[
            (role, key, span)]
    return ctx


def reduced_known():
    planes = known_planes()
    out = trace_reduce.reduce_planes(planes, extent=(0, 10 * MS))
    out.update(span_reduce.reduce_spans(planes, extent=(0, 10 * MS)))
    return out


def test_readers_on_known_intervals():
    ctx = context(reduced_known())
    assert span_readers.span_ms_per_mrec(
        ctx, "task.process_batch", "source") == pytest.approx(2.25)
    assert span_readers.idle_share_under(
        ctx, ["checkpoint.snapshot", "window_agg.snapshot",
              "window_agg.snapshot_d2h"]) == pytest.approx(500 / 7)
    assert span_readers.idle_unattributed_share(ctx) \
        == pytest.approx(100 / 7)
    # 1 ms of device time, one snapshot_d2h on two subtasks: half a cut
    assert span_readers.module_ms(
        ctx, "_snapshot_read_step", "window_agg.snapshot_d2h") \
        == pytest.approx(2.0)


@pytest.mark.parametrize("reader, params, match", [
    ("span_ms_per_mrec", {"span": "exchange.partition", "role": "source"},
     "exchange.partition"),
    ("idle_share_under", {"spans": ["checkpoint.snapshot",
                                    "window_agg.snapshot_assemble"]},
     "window_agg.snapshot_assemble"),
    ("module_ms", {"module": "_fire_gather_step",
                   "per": "window_agg.snapshot_d2h"}, "_fire_gather_step"),
    ("module_ms", {"module": "_snapshot_read_step", "per": "window_agg.fire"},
     "window_agg.fire"),
])
def test_a_reader_without_its_span_or_module_raises(reader, params, match):
    with pytest.raises(RuntimeError, match=match):
        getattr(span_readers, reader)(context(reduced_known()), **params)


def test_a_trace_of_the_parent_has_no_span_to_call_unattributed():
    """The recorded trace of PR 24's program holds the dispatch annotation
    and the harness's two spans; a trace with none at all is an error."""
    bare = [(name, [(line, [ev for ev in events if ev[0] not in
                            span_reduce.SPANS]) for line, events in lines])
            for name, lines in known_planes()]
    out = trace_reduce.reduce_planes(bare, extent=(0, 10 * MS))
    out.update(span_reduce.reduce_spans(bare, extent=(0, 10 * MS)))
    assert out["spans"] == {} and out["idle_unattributed_s"] == out["idle_s"]
    with pytest.raises(RuntimeError, match="no program span"):
        span_readers.idle_unattributed_share(context(out))


def test_the_accepted_reduction_is_untouched():
    """Through this module's reader (host lines with every span) the
    accepted reduction gives what it gives through its own, to the byte,
    and the recorded trace still reduces to its expected numbers."""
    mine = trace_reduce.reduce_planes(*span_reduce.read_planes(OLD_FIXTURE))
    theirs = trace_reduce.reduce_planes(*trace_reduce.read_planes(OLD_FIXTURE))
    assert json.dumps(mine, sort_keys=True) == json.dumps(theirs,
                                                          sort_keys=True)
    with open(os.path.join(HERE, "data", "expected.json")) as f:
        want = json.load(f)
    assert theirs["busy_s"] == pytest.approx(want["busy_s"], rel=1e-3)
    assert theirs["window_s"] == pytest.approx(want["window_s"], rel=1e-6)
    assert theirs["modules"]["_update_step"]["runs"] \
        == want["update_step_runs"]
    # PR 24's program: one program span, which is a leaf here too
    spans = span_reduce.reduce_spans(*span_reduce.read_planes(OLD_FIXTURE))
    assert set(spans["spans"]) == {"window_agg.device_step",
                                   "bench.source.next"}


def test_reduce_the_recorded_trace_of_the_span_layer():
    """A slice of a chip run of PR 26's program at the tests' size (2^12
    keys, paced, one cut and one fire in it); the expected numbers beside it
    were worked out by rasterising device events and spans onto a
    microsecond grid (`how`)."""
    with open(os.path.join(HERE, "data", "spans_expected.json")) as f:
        want = json.load(f)
    planes, extent = span_reduce.read_planes(FIXTURE)
    out = span_reduce.reduce_spans(planes, extent)
    assert out["idle_s"] == pytest.approx(want["idle_s"], rel=1e-3)
    assert out["idle_unattributed_s"] == pytest.approx(
        want["idle_unattributed_s"], rel=1e-3, abs=2e-6)
    for name, row in want["spans"].items():
        got = out["spans"][name]
        assert got["count"] == row["count"], name
        assert got["seconds"] == pytest.approx(row["seconds"], rel=1e-3,
                                               abs=2e-6), name
        assert got["idle_s"] == pytest.approx(row["idle_s"], rel=1e-3,
                                              abs=2e-6), name
    assert set(out["spans"]) == set(want["spans"])
    assert span_reduce.idle_under(out, want["idle_under"]["spans"]) \
        == pytest.approx(want["idle_under"]["seconds"], rel=1e-3, abs=2e-6)
    modules = trace_reduce.reduce_planes(planes, extent)["modules"]
    assert sorted(modules) == want["modules"]
    # one cut (four tasks snapshot, two of them window state, one store)
    # and one fire (two subtasks) in the slice
    assert out["spans"]["checkpoint.snapshot"]["count"] == 4
    assert out["spans"]["window_agg.snapshot"]["count"] == 2
    assert out["spans"]["checkpoint.store"]["count"] == 1
    assert out["spans"]["window_agg.fire"]["count"] == 2


def test_span_metrics_fit_the_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    added = runner.load_json("span_metrics.json")
    layers = {m["layer"] for m in bench["per_layer"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    accepted = {m["name"] for m in bench["per_layer"]}
    assert set(added["cells"]) == {c["name"] for c in bench["workloads"]}
    used = set()
    for cell, names in added["cells"].items():
        spec = runner.load_json("workloads", f"{cell}.json")
        assert not set(names) & (accepted | set(spec["per_layer"]))
        used |= set(names.values())
        for name, stem in names.items():
            moves = added["files"][stem].get(
                "moves", "result_latency_p50_ms" if cell.endswith(".paced")
                else "records_per_s")
            assert cell in e2e[moves]["workloads"], (name, cell)
    assert used == set(added["files"])
    for stem, spec in added["files"].items():
        assert spec["layer"] in layers, stem
        assert spec["source"] in ("program_span", "device_trace")
        assert hasattr(span_readers, spec["reader"]) \
            or hasattr(readers, spec["reader"]), stem


def test_a_traced_line_holds_the_span_metrics_too(monkeypatch):
    """`spans.install` hands the accepted runner the new metrics beside the
    cell's own: a whole run on the CPU whose device trace is the recorded
    one."""
    reduced = span_reduce.reduce_spans(*span_reduce.read_planes(FIXTURE))
    reduced.update(trace_reduce.reduce_planes(
        *span_reduce.read_planes(FIXTURE)))
    # what `install` replaces goes back when the test ends
    monkeypatch.setattr(runner, "layer_metrics", runner.layer_metrics)
    monkeypatch.setattr(trace_reduce, "reduce_dir", trace_reduce.reduce_dir)
    for reader in ("span_ms_per_mrec", "idle_share_under",
                   "idle_unattributed_share", "module_ms"):
        monkeypatch.setattr(readers, reader, None, raising=False)
    cell = "tumbling-sum-1m.backlog"
    names = spans_cli.install(cell, say=lambda _m: None)
    monkeypatch.setattr(runner.jax.profiler, "start_trace",
                        lambda *a, **k: None)
    monkeypatch.setattr(runner.jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(span_reduce, "reduce_dir", lambda _d: reduced)
    monkeypatch.setattr(span_reduce, "find_xplane", lambda _d: FIXTURE)
    monkeypatch.setattr(readers, "load_peaks", lambda _k: {
        "hbm_bytes_per_s": 819e9, "flops_per_s": 197e12})
    over = dict(small(cell), cell={"trace_slice": {"start_s": 0.3,
                                                   "length_s": 1.0}})
    line = runner.run_cell(cell, 2**31 + 5, 2.0, True, time.monotonic(),
                           overrides=over, say=lambda _m: None)
    spec = runner.load_json("workloads", f"{cell}.json")
    assert set(line["metrics"]) == set(spec["per_layer"]) | set(names)
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # the new phases split what the accepted metric sums, and `stage` is
    # the time that was in no phase
    assert m["fold_host_self_ms_per_mrec"] + m["dispatch_host_ms_per_mrec"] \
        > m["fold_host_ms_per_mrec"]
