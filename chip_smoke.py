#!/usr/bin/env python3
"""chip_smoke.py — the keyed-window job path, end to end, on the TPU.

The quickest proof that the program still starts on the chip: one process,
no children, drives source -> key_by -> tumbling window -> keyed sum -> sink
through ``StreamExecutionEnvironment`` at the size of BASELINE.json config 2
(2^20 int64 keys, 5 s windows, batches of 2^18), checks every lane's rows
against a plain numpy reference, and prints a ``summary:`` line (per-phase
wall seconds, ``"claim": null``) and then, as the last line of its standard
output, exactly

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

Any failed phase raises; nothing is caught and carried past.  It exits
non-zero without that line when JAX finds no TPU (``JAX_PLATFORMS=cpu``
included) or the native library did not build.  The seconds it prints are
set-up and wall time of a smoke, not metrics; it prints no rate.

Phases: default lane (what a user gets), device-authoritative lane
(``emit_tier="device"``), the cluster
runtime (``execute_cluster`` at parallelism 2: task threads sharing
the one chip, checkpoints completing mid-run; default options, then the
device tier) and, when four TPU devices are visible or ``--devices 4`` is
given, the mesh runtime on four chips.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

WINDOW_MS = 5000
N_WINDOWS = 7          # event time spans seven windows: at least six fire
                       # on watermarks, the last at end of input
CHECKPOINT_INTERVAL_MS = 200   # cluster phase: several cuts at full size


def check(cond, msg: str) -> None:
    """Phase assertion that survives ``python -O``."""
    if not cond:
        raise RuntimeError(f"chip_smoke: {msg}")


# ---------------------------------------------------------------------------
# data + plain reference (numpy only: no kernels, no batching)
# ---------------------------------------------------------------------------

class Events:
    """``n_batches`` batches of ``batch`` events over ``n_keys`` distinct
    int64 keys drawn uniformly, f32 values, in-order event time spanning
    ``N_WINDOWS`` windows — and the f64 group-by-(key, window) reference."""

    def __init__(self, seed: int, n_keys: int, batch: int, n_batches: int):
        rng = np.random.default_rng(seed)
        universe = np.unique(rng.integers(1, 1 << 62, n_keys, dtype=np.int64))
        while universe.size < n_keys:   # 2^20 draws from 2^62: ~never
            universe = np.unique(np.concatenate(
                [universe, rng.integers(1, 1 << 62, n_keys, dtype=np.int64)]
            ))[:n_keys]
        self.universe = universe        # sorted: searchsorted maps back
        n = batch * n_batches
        kidx = rng.integers(0, n_keys, n)
        self.columns = {
            "k": universe[kidx],
            "v": rng.random(n, dtype=np.float32),
            "ts": np.arange(n, dtype=np.int64) * (N_WINDOWS * WINDOW_MS) // n,
        }
        self.batch = batch
        cell = kidx * N_WINDOWS + self.columns["ts"] // WINDOW_MS
        cells = n_keys * N_WINDOWS
        self.ref_sum = np.bincount(
            cell, weights=self.columns["v"].astype(np.float64),
            minlength=cells)
        self.ref_cells = np.flatnonzero(np.bincount(cell, minlength=cells))

    def cells_of(self, sink):
        """(sorted cell ids, their emitted values) of a job's output, read
        back columnar; fails on unknown keys, bad bounds, duplicates."""
        k = sink.column("k")
        start = sink.column("window_start")
        check(k.size > 0, "job emitted no rows")
        pos = np.searchsorted(self.universe, k)
        check(pos.max() < self.universe.size
              and np.array_equal(self.universe[pos], k),
              "emitted a key that was never sent")
        check(np.array_equal(sink.column("window_end"), start + WINDOW_MS)
              and not (start % WINDOW_MS).any(), "bad window bounds")
        cell = pos * N_WINDOWS + start // WINDOW_MS
        order = np.argsort(cell, kind="stable")
        cell = cell[order]
        check(not (cell[1:] == cell[:-1]).any(),
              "a (key, window) was emitted twice")
        return cell, np.asarray(sink.column("result"))[order]

    def check_rows(self, sink):
        """Same (key, window) set as the reference, values within f32
        accumulation tolerance of the f64 sums."""
        cell, got = self.cells_of(sink)
        check(np.array_equal(cell, self.ref_cells),
              f"(key, window) set differs from the reference: "
              f"{cell.size} rows vs {self.ref_cells.size}")
        check(np.isfinite(got).all(), "non-finite result")
        check(np.allclose(got, self.ref_sum[cell], rtol=1e-4, atol=1e-4),
              "values differ from the numpy reference")
        return cell, got


# ---------------------------------------------------------------------------
# the job, through the public API
# ---------------------------------------------------------------------------

def build_job(env, ev: Events, **agg_options):
    """source -> timestamps/watermarks -> key_by -> 5 s tumbling window ->
    f32 sum -> collect; returns the CollectSink."""
    import jax.numpy as jnp

    from flink_tpu.core.functions import SumAggregator
    from flink_tpu.windowing.assigners import TumblingEventTimeWindows

    return (env.from_collection(columns=ev.columns, batch_size=ev.batch)
            .assign_timestamps_and_watermarks(0, timestamp_column="ts")
            .key_by("k")
            .window(TumblingEventTimeWindows.of(WINDOW_MS))
            .aggregate(SumAggregator(jnp.float32), value_column="v",
                       **agg_options)
            .collect())


def window_operators(operators):
    """The WindowAggOperator instances among (possibly chained) operators."""
    from flink_tpu.operators.window_agg import WindowAggOperator

    found = []
    for op in operators:
        for member in getattr(op, "operators", [op]):
            if isinstance(member, WindowAggOperator):
                found.append(member)
    check(found, "no window operator in the executed plan")
    return found


def run_local(ev: Events, mesh_devices=None, **agg_options):
    """One job through ``env.execute()``; returns (sink, window operator)."""
    from flink_tpu.datastream.api import StreamExecutionEnvironment

    env = StreamExecutionEnvironment()
    if mesh_devices:
        env.set_mesh(n_devices=mesh_devices)
    sink = build_job(env, ev, **agg_options)
    env.execute("chip-smoke")
    (op,) = window_operators(
        rv.operator for rv in env._last_executor.running.values())
    return sink, op


def report_lanes(op) -> None:
    """What the operator resolved to — findings for ROADMAP A1/C1."""
    from flink_tpu.utils import transport

    print(f"  emit_tier={op.emit_tier} device_sync_mode={op.device_sync_mode}"
          f" native_mirror={op._nm is not None}")
    print(f"  hot_dispatches={op.fused_stats()['hot_dispatches']}")
    print(f"  phase_bytes h2d={op.phase_bytes.get('h2d', 0)} "
          f"d2h={op.phase_bytes.get('d2h', 0)}")
    print(f"  transport.dispatch_ms_per_mb={transport.dispatch_ms_per_mb()} "
          f"taxed={transport.dispatch_taxed()}")
    print(f"  device_health={op.device_health_stats()}")
    print("  operator phase wall ms (host clock, compiles included): "
          + str({k: round(v / 1e6) for k, v in sorted(op.phase_ns.items())}))


def check_healthy(ops) -> None:
    from flink_tpu.runtime import device_health

    for op in ops:
        stats = op.device_health_stats()
        check(not stats["degraded"] and not stats["quarantine_migrations"],
              f"operator left the device tier: {stats}")
    status = device_health.status_snapshot()
    check(status["state"] == "healthy" and status["quarantines"] == 0,
          f"device monitor quarantined: {status}")


def state_arrays(op):
    check(op._leaves is not None and op._counts is not None,
          "operator holds no device state")
    return [*op._leaves, op._counts]


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_default(ev: Events):
    sink, op = run_local(ev)
    report_lanes(op)
    check_healthy([op])
    return ev.check_rows(sink)


def check_state_placement(op, n_devices: int) -> None:
    """The state sits on ``n_devices`` distinct devices, a 1/n block of the
    key rows each, and each device's memory shows it."""
    arrays = state_arrays(op)
    devices = set()
    for a in arrays:
        shards = a.addressable_shards
        check(len(shards) == n_devices, f"{len(shards)} shards")
        for s in shards:
            check(s.data.shape[0] * n_devices == a.shape[0],
                  f"shard holds {s.data.shape[0]} of {a.shape[0]} key rows")
        devices |= {s.device for s in shards}
    check(len(devices) == n_devices,
          f"state sits on {len(devices)} device(s), wanted {n_devices}")
    shard_bytes = sum(a.nbytes for a in arrays) // n_devices
    for d in sorted(devices, key=lambda d: d.id):
        stats = d.memory_stats()
        print(f"  {d}: holds {shard_bytes} B of state, memory_stats={stats}")
        if stats is None:
            check(d.platform == "cpu", "accelerator reports no memory_stats")
        else:
            check(stats["bytes_in_use"] >= shard_bytes,
                  f"{d} holds less than its share of the state")


def phase_device(ev: Events) -> None:
    sink, op = run_local(ev, emit_tier="device")
    report_lanes(op)
    check_healthy([op])
    check(op.emit_tier == "device", "device tier did not hold")
    check(sum(a.nbytes for a in state_arrays(op))
          >= ev.universe.size * op._P * 8, "state smaller than keys x panes")
    check_state_placement(op, 1)
    ev.check_rows(sink)


def phase_cluster(ev: Events, **agg_options) -> None:
    """The job on the MiniCluster at parallelism 2 — two source and two
    window task threads sharing the one chip — with periodic checkpoints."""
    from flink_tpu.datastream.api import StreamExecutionEnvironment
    from flink_tpu.runtime.checkpoint.storage import InMemoryCheckpointStorage

    env = StreamExecutionEnvironment(parallelism=2)
    sink = build_job(env, ev, **agg_options)
    # two-element channels: the bounded source runs under the window
    # tasks' backpressure, so barriers travel mid-stream, not after it
    res = env.execute_cluster("chip-smoke-cluster",
                              storage=InMemoryCheckpointStorage(),
                              checkpoint_interval_ms=CHECKPOINT_INTERVAL_MS,
                              channel_capacity=2, timeout_s=900.0)
    completed = list(res.completed_checkpoints)   # as of job end
    check(res.state == "FINISHED", f"cluster job {res.state}: {res.error}")
    print(f"  completed checkpoints: {completed}")
    check(completed, "no checkpoint completed mid-run")
    ops = window_operators(t.operator for t in env._last_cluster._tasks)
    check(len(ops) == 2, f"expected 2 window subtasks, found {len(ops)}")
    for op in ops:
        report_lanes(op)
        if agg_options.get("emit_tier") == "device":
            check(op.fused_stats()["hot_dispatches"] > 1,
                  "a window task thread never dispatched to the chip")
    check_healthy(ops)
    ev.check_rows(sink)


def phase_mesh(ev: Events, n_devices: int, one_chip) -> None:
    """The job with ``env.set_mesh(n_devices=N)`` on the mesh operator's
    host tier, asked for by name (a mesh job's own pick is the device tier,
    below): one process drives all N chips; rows equal the one-chip run."""
    sink, op = run_local(ev, mesh_devices=n_devices, emit_tier="host")
    report_lanes(op)
    check_healthy([op])
    check_state_placement(op, n_devices)
    cell, got = ev.check_rows(sink)
    check(np.array_equal(cell, one_chip[0])
          and np.allclose(got, one_chip[1], rtol=1e-6, atol=1e-6),
          "mesh rows differ from the one-chip run")
    print(f"  bit-identical to the one-chip run: "
          f"{bool(np.array_equal(got, one_chip[1]))}")


def phase_mesh_scatter(ev: Events, n_devices: int) -> None:
    """The mesh job on the device tier (``emit_tier="device"``, which is
    also what a mesh job resolves to when nothing is asked for): every
    batch rides the ``all_to_all`` exchange into the sharded device state,
    and every window fires from it, so the rows check the chips' fold."""
    sink, op = run_local(ev, mesh_devices=n_devices, emit_tier="device")
    report_lanes(op)
    check_healthy([op])
    check_state_placement(op, n_devices)
    check(op.emit_tier == "device" and op.device_sync_mode == "scatter",
          f"lanes resolved to {op.emit_tier} / {op.device_sync_mode}")
    check(op.fused_stats()["hot_dispatches"] > 1,
          "the operator never dispatched to the device")
    check(op.phase_bytes.get("exchange_live", 0) > 0,
          "no record crossed the exchange")
    ev.check_rows(sink)


def run_phases(args) -> dict:
    """Every phase in order; raises on the first failure.  Returns
    {phase: wall seconds}."""
    import jax

    from flink_tpu.utils import transport

    n_keys, batch = 1 << args.keys_log2, 1 << args.batch_log2
    walls = {}

    def phase(name, fn, *a, **kw):
        print(f"== {name}", flush=True)
        t0 = time.monotonic()
        out = fn(*a, **kw)
        walls[name] = round(time.monotonic() - t0, 1)
        print(f"== {name}: ok, {walls[name]} s wall (compiles included; "
              f"not a metric)", flush=True)
        return out

    t0 = time.monotonic()
    ev = Events(args.seed, n_keys, batch, args.batches)
    print(f"events: {batch * args.batches} over {n_keys} keys, "
          f"{ev.ref_cells.size} (key, window) cells, made in "
          f"{time.monotonic() - t0:.1f} s (set-up)")
    one_chip = phase("default lane", phase_default, ev)
    measured = transport.dispatch_taxed()
    phase("device-authoritative lane", phase_device, ev)
    # the cluster runs the default options as a fresh process would
    # (calibrating for itself), then the device-authoritative lane, where
    # both window task threads dispatch every batch to the chip and the
    # checkpoints download device state
    transport.reset()
    phase("cluster runtime", phase_cluster, ev)
    phase("cluster runtime, device tier", phase_cluster, ev,
          emit_tier="device")
    transport.reset(verdict=measured)
    n_mesh = args.devices or (4 if len(jax.devices()) >= 4 else 0)
    if n_mesh > 1:
        phase(f"{n_mesh} chips", phase_mesh, ev, n_mesh, one_chip)
        phase(f"{n_mesh} chips, scatter sync", phase_mesh_scatter, ev,
              n_mesh)
    else:
        print("== four chips: skipped, one device visible")
    return walls


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def result_line(devices) -> str:
    """The last line of standard output: these keys and no others — the
    driver's chip check parses it."""
    dev = devices[0]
    return json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices)},
    })


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=21)
    p.add_argument("--devices", type=int, default=0,
                   help="run the mesh phase on this many devices (default: "
                        "4 when four are visible)")
    p.add_argument("--keys-log2", type=int, default=20)
    p.add_argument("--batch-log2", type=int, default=18)
    p.add_argument("--batches", type=int, default=32)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    t_start = time.monotonic()
    from flink_tpu.utils.platform import configure_compile_cache

    cache_dir = configure_compile_cache()
    from importlib import metadata

    import jax

    print(" ".join(f"{pkg} {metadata.version(pkg)}"
                   for pkg in ("jax", "jaxlib", "libtpu")))
    devices = jax.devices()
    dev = devices[0]
    entries_before = cache_entries(cache_dir)
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"count={len(devices)}")
    print(f"compile cache: {cache_dir} ({entries_before} entries)")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    from flink_tpu import native

    print(f"native_available={native.native_available()}")
    if not native.native_available():
        print(f"chip_smoke: native layer did not build: "
              f"{native.build_error()}", file=sys.stderr)
        return 3
    walls = run_phases(args)
    print(f"compile cache: {cache_dir} ({entries_before} -> "
          f"{cache_entries(cache_dir)} entries)")
    print(f"total wall {time.monotonic() - t_start:.1f} s (set-up and "
          f"compiles included; not a metric)")
    print("summary: " + json.dumps({"phase_wall_s": walls, "claim": None}))
    print(result_line(devices), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
