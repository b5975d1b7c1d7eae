"""The files of `layer_metrics/`, run by hand (not a tier-1 test):

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_layer_files.py -q

Every file names a reader that `harness/readers.py` has and parameters it
takes.  The files PR 37 added are in no cell's map yet (PERF.md, Open
question 13, step (h)): a small run of a one-chip cell and of a mesh cell
on the CPU, with the cell's map extended through `run_cell`'s `overrides`,
shows that each gives a number.  The four devices of the mesh cell have to
be asked for before JAX starts: this file does so when it is the first to
import JAX, and skips otherwise.
"""

from __future__ import annotations

import inspect
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

from harness import readers, runner, trace_reduce  # noqa: E402

#: the files that read what PR 37 added to `phase_ns`, and the module a
#: cell's recorded one-chip trace has to show under (the CPU has none)
NEW = ["fold_host_self_ms_per_mrec", "fold_host_cpu_ms_per_mrec",
       "launch_host_ms_per_mrec", "dispatch_handoff_ms_per_mrec",
       "window_op_batch_ms_per_mrec", "window_op_batch_cpu_ms_per_mrec",
       "fire_assemble_cpu_ms"]
MESH_ONLY = ["exchange_route_cpu_ms_per_mrec"]
CELLS = {"tumbling-sum-1m.backlog": ("_update_step", NEW),
         "tumbling-sum-1m-mesh4.backlog": ("_mesh_update_step",
                                           NEW + MESH_ONLY)}


def layer_files():
    return sorted(f[:-5] for f in
                  os.listdir(os.path.join(BENCH, "layer_metrics")))


def test_the_new_files_are_there():
    assert set(NEW + MESH_ONLY) <= set(layer_files())


@pytest.mark.parametrize("stem", layer_files())
def test_a_file_names_a_reader_and_its_parameters(stem):
    spec = runner.load_json("layer_metrics", f"{stem}.json")
    assert set(spec) == {"layer", "unit", "better", "source", "reader",
                         "params"}
    assert spec["better"] in ("lower", "higher")
    assert spec["source"] in ("device_trace", "program_span",
                              "program_counter", "host_clock")
    reader = getattr(readers, spec["reader"])
    takes = inspect.signature(reader).parameters
    assert list(takes)[0] == "ctx"
    assert set(spec["params"]) == set(takes) - {"ctx"}


def small(cell_name: str) -> dict:
    _, config, _ = runner.load_cell(cell_name)
    return {
        "config": {"keys": {"count": 4096}, "batch_events": 256,
                   "guarantees": dict(config["guarantees"],
                                      checkpoint_interval_ms=500)},
        "traffic": {"events_per_slide": 16384},
    }


@pytest.mark.parametrize("cell_name", sorted(CELLS))
def test_each_new_file_gives_a_number_in_a_small_run(monkeypatch, cell_name):
    """The cell's own map plus the new files, through `overrides`; the
    device trace is the recorded one-chip one (under the mesh step's name
    for the mesh cell)."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs four devices: run this file alone, or with "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=4")
    module, new = CELLS[cell_name]
    path = os.path.join(HERE, "data", "tumbling-sum-1m.backlog.xplane.pb")
    reduced = trace_reduce.reduce_planes(*trace_reduce.read_planes(path))
    reduced["modules"][module] = reduced["modules"].pop("_update_step")
    monkeypatch.setattr(runner.jax.profiler, "start_trace",
                        lambda *a, **k: None)
    monkeypatch.setattr(runner.jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(trace_reduce, "reduce_dir", lambda _d: reduced)
    monkeypatch.setattr(readers, "load_peaks", lambda _k: {
        "hbm_bytes_per_s": 819e9, "flops_per_s": 197e12})
    cell = runner.load_json("workloads", f"{cell_name}.json")
    per_layer = dict(cell["per_layer"], **{name: name for name in new})
    line = runner.run_cell(
        cell_name, 2**31 + 37, 2.0, True, time.monotonic(),
        overrides=dict(small(cell_name), cell={
            "per_layer": per_layer,
            "trace_slice": {"start_s": 0.3, "length_s": 1.0}}),
        say=lambda _m: None)
    assert line["correct"], line["compared"]
    assert set(line["metrics"]) == set(per_layer)
    got = {name: line["metrics"][name]["value"] for name in new}
    assert all(value > 0 for value in got.values()), got
    # CPU time inside wall time, part inside whole
    assert got["fold_host_cpu_ms_per_mrec"] \
        <= got["fold_host_self_ms_per_mrec"]
    assert got["window_op_batch_cpu_ms_per_mrec"] \
        <= got["window_op_batch_ms_per_mrec"]
    whole = line["metrics"]["fold_host_ms_per_mrec"]["value"]
    assert got["launch_host_ms_per_mrec"] \
        + got["dispatch_handoff_ms_per_mrec"] < whole \
        < got["window_op_batch_ms_per_mrec"]
    if cell_name.endswith("mesh4.backlog"):
        assert got["exchange_route_cpu_ms_per_mrec"] <= line["metrics"][
            "exchange_route_host_ms_per_mrec"]["value"]
