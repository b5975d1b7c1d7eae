"""CPU rehearsal of `sql-tumble-multiagg-1m-mesh4.backlog` at 2^12 keys, on four
forced host devices, run by hand (not a tier-1 test):

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_sql_mesh_cell.py -q

`run_cell` is driven directly, past the CLI's look for a chip.  The four
devices have to be asked for before JAX starts: this file does so when it is
the first to import JAX, and skips otherwise.
"""

from __future__ import annotations

import json
import os
import sys
import time

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = \
        flags + " --xla_force_host_platform_device_count=4"

import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

import jobs.sql_group_window as job  # noqa: E402
from harness import compare, readers, runner, trace_reduce  # noqa: E402
from harness.generator import Stream  # noqa: E402
from reference.sql_group_window import Reference  # noqa: E402

CELL = "sql-tumble-multiagg-1m-mesh4.backlog"
#: what the deployment adds to every run's `phase_bytes` line
COUNTERS = ("exchange_value_leaves", "fire_dense_cells",
            "snapshot_column_reads")


@pytest.fixture(autouse=True)
def four_devices():
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs four devices: run this file alone, or with "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=4")


def small() -> dict:
    _, config, _ = runner.load_cell(CELL)
    return {
        "config": {"keys": {"count": 4096}, "batch_events": 256,
                   "guarantees": dict(config["guarantees"],
                                      checkpoint_interval_ms=500)},
        "traffic": {"events_per_slide": 16384},
    }


def phase_bytes(said) -> dict:
    head = "window operator 0: phase_bytes "
    return json.loads(next(m for m in said if m.startswith(head))[len(head):])


def test_rows_equal_the_reference_and_the_lane_holds():
    said = []
    line = runner.run_cell(CELL, 2**31 + 35, 2.0, False, time.monotonic(),
                           overrides=small(), say=said.append)
    cell = runner.load_json("workloads", f"{CELL}.json")
    assert line["correct"], line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    # AVG is held to `sum_rel_gap` beside SUM; the exact kinds were looked at
    assert set(line["compared"]) == set(cell["limits"])
    for name in ("rows_missing", "rows_unexpected", "count_mismatch",
                 "minmax_mismatch"):
        assert line["compared"][name]["value"] == 0, line["compared"]
    assert set(line["metrics"]) == set(cell["end_to_end"])
    assert line["device"]["count"] >= cell["chips"] == 4
    lanes = [m for m in said if m.startswith("window operator 0: lanes")]
    assert '"emit_tier": "device"' in lanes[0]
    assert '"device_sync_mode": "scatter"' in lanes[0]
    in_window = [m for m in said if "inside the window" in m]
    assert "'programs': 0" in in_window[0], in_window
    # the plan ships one `<alias>_in` column per aggregate call with an
    # argument and `__ones` for COUNT(*): five value leaves a batch
    counted = phase_bytes(said)
    for name in COUNTERS:
        assert counted[name] > 0, name
    assert counted["exchange_value_leaves"] \
        == 5 * counted["exchange_route_batches"]


def test_a_traced_line_holds_the_cells_layer_metrics(monkeypatch):
    """The readers run on a whole run's counters; the device trace is the
    recorded one-chip one with its module under the mesh step's name (the
    CPU has none)."""
    path = os.path.join(HERE, "data", "tumbling-sum-1m.backlog.xplane.pb")
    reduced = trace_reduce.reduce_planes(*trace_reduce.read_planes(path))
    reduced["modules"]["_mesh_update_step"] = \
        reduced["modules"].pop("_update_step")
    monkeypatch.setattr(runner.jax.profiler, "start_trace",
                        lambda *a, **k: None)
    monkeypatch.setattr(runner.jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(trace_reduce, "reduce_dir", lambda _d: reduced)
    monkeypatch.setattr(readers, "load_peaks", lambda _k: {
        "hbm_bytes_per_s": 819e9, "flops_per_s": 197e12})
    line = runner.run_cell(CELL, 2**31 + 6, 2.0, True, time.monotonic(),
                           overrides=dict(small(), cell={"trace_slice": {
                               "start_s": 0.3, "length_s": 1.0}}),
                           say=lambda _m: None)
    cell = runner.load_json("workloads", f"{CELL}.json")
    assert len(cell["per_layer"]) == 11
    assert set(line["metrics"]) == set(cell["per_layer"])
    for name in ("exchange_route_host_ms_per_mrec", "fire_host_ms.backlog",
                 "fold_host_ms_per_mrec", "snapshot_host_ms",
                 "mesh_update_step_roofline", "dense_fire_d2h_wait_ms",
                 "dense_fire_assemble_host_ms", "stage_host_ms_per_mrec"):
        assert line["metrics"][name]["value"] > 0, name
    # the d2h wait and the assembly are parts of the fire
    assert line["metrics"]["dense_fire_d2h_wait_ms"]["value"] \
        + line["metrics"]["dense_fire_assemble_host_ms"]["value"] \
        < line["metrics"]["fire_host_ms.backlog"]["value"]


@pytest.mark.parametrize("mode,correct", [
    ("exact", True), ("bf16", False), ("replay", False), ("drop", False)])
def test_control_fails_the_comparison(mode, correct):
    cell, config, traffic = runner.load_cell(CELL)
    over = small()
    config.update(over["config"])
    traffic.update(over["traffic"])
    stream = Stream(config, traffic, 11)
    fields = job.output_fields(config)
    sent = list(range(stream.warm_batches + 3 * stream.batches_per_slide))
    rows = compare.control_rows(stream, Reference(config), fields, sent, mode,
                                pick=stream.warm_batches + 7)
    result = compare.compare(stream, Reference(config), fields, sent, rows)
    numbers, ok = compare.verdict(result.numbers, cell["limits"])
    assert ok is correct, numbers
