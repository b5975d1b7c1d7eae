"""North-star benchmark: 1M-key tumbling windowed sum (BASELINE.json).

Measures records/sec/chip of the TPU-native WindowAggOperator hot path —
batched scatter-combine on device state plus the write-through HOST emit
tier serving window fires (the replacement for the reference's per-record
``WindowOperator.processElement`` → ``HeapAggregatingState`` loop and its
``emitWindowContents`` fire path) — in the CHECKPOINTABLE configuration:
synchronous fires, mid-run snapshots taken inside the timed region, and a
restore+replay equivalence check after the run.

Baselines (the reference publishes no absolute numbers — BASELINE.md):
- ``heap``: single-threaded per-record Python dict loop (the driver-defined
  HeapStateBackend analog; ``vs_baseline`` is against this).
- ``numpy``: a competent vectorized single-core CPU implementation (same
  C++ key index, bincount accumulation, vectorized fires) — published so
  the device path is compared against a strong CPU contender, not only the
  interpreted loop (VERDICT r2 weak #4).

Emit-tier note: the operator's ``emit_tier="host"`` keeps a write-through
host value mirror of the ACC cells (see ``operators/window_agg.py``) so
fires and snapshots ship zero device->host bytes; the device state stays
the authoritative sharded copy and is verified against the mirror after
the run (``verify_mirror``, a real device download).  What a fire-time
download costs is unmeasured on a directly attached chip — ROADMAP A1.
The per-phase breakdown below makes the split between host work, uploads,
and device work explicit.

A run that was not told ``JAX_PLATFORMS=cpu`` and finds no accelerator
fails; there is no CPU fallback.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from flink_tpu.utils.platform import configure_compile_cache  # noqa: E402

configure_compile_cache()


def _early_mesh_device_flags() -> None:
    """``--mesh-devices N`` on a CPU target needs
    ``--xla_force_host_platform_device_count=N`` BEFORE the first backend
    init (argparse runs after module import, so peek at argv here) — the
    laptop/CI recipe for exercising real multi-device sharding without a
    pod (docs/operations.md "Multi-chip execution")."""
    argv = sys.argv
    n = 0
    for i, a in enumerate(argv):
        if a == "--mesh-devices" and i + 1 < len(argv):
            n = int(argv[i + 1])
        elif a.startswith("--mesh-devices="):
            n = int(a.split("=", 1)[1])
    if n > 1 and os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n}")


_early_mesh_device_flags()


def _pick_native_shards() -> int:
    """The operator's own process-wide shard calibration (measured serial
    vs parallel on a throwaway mirror — see
    ``state/native_mirror.calibrated_shards``), surfaced here so the bench
    prints the pick before the run."""
    from flink_tpu.state.native_mirror import calibrated_shards

    pick = calibrated_shards()
    print(f"# native-shards calibration -> {pick}", file=sys.stderr)
    return pick


def make_batches(n_records: int, n_keys: int, batch_size: int, window_ms: int,
                 seed: int = 7):
    rng = np.random.default_rng(seed)
    batches = []
    t = 0
    for lo in range(0, n_records, batch_size):
        b = min(batch_size, n_records - lo)
        keys = rng.integers(0, n_keys, b).astype(np.int64)
        vals = rng.random(b).astype(np.float32)
        # event time advances ~1ms per 1k records -> several windows per run
        ts = t + np.sort(rng.integers(0, 1000, b)).astype(np.int64)
        t += 1000
        batches.append((keys, vals, ts))
    return batches


def _build_op(window_ms: int, emit_tier: str = "host",
              device_sync: str = "auto", paging_cap: int = 0,
              pipeline_depth: int = 1, native_shards: int = 0,
              mesh_devices: int = 0, key_capacity: int = 1 << 20,
              queryable=None):
    import jax.numpy as jnp

    from flink_tpu.core.functions import RuntimeContext, SumAggregator
    from flink_tpu.operators.window_agg import WindowAggOperator
    from flink_tpu.windowing.assigners import TumblingEventTimeWindows

    paging = None
    if paging_cap:
        from flink_tpu.state.paging import PagingConfig
        paging = PagingConfig(capacity=paging_cap)
        emit_tier = "device"   # paging pins the device tier
    kw = dict(
        key_column="k", value_column="v",
        initial_key_capacity=key_capacity,
        emit_tier=emit_tier,
        snapshot_source="mirror" if emit_tier == "host" else "device",
        device_sync=device_sync if emit_tier == "host" else "scatter",
        paging=paging,
        # the bench IS the hot-path deployment: pipelined by default
        # (--pipeline-depth 0 A/Bs the serial path), native probe sharded
        # across cores (--native-shards; 0 = auto)
        pipeline_depth=pipeline_depth,
        native_shards=native_shards,
        queryable=queryable)
    if mesh_devices > 1:
        # the mesh-sharded hot path: ONE logical operator over the chip
        # mesh (parallel/mesh_runtime) — state in key-group-range blocks,
        # records routed by on-device all_to_all, probe sharded per device
        from flink_tpu.parallel.mesh import make_mesh
        from flink_tpu.parallel.mesh_runtime import MeshWindowAggOperator
        op = MeshWindowAggOperator(
            TumblingEventTimeWindows.of(window_ms),
            SumAggregator(jnp.float32), mesh=make_mesh(mesh_devices), **kw)
    else:
        op = WindowAggOperator(
            TumblingEventTimeWindows.of(window_ms),
            SumAggregator(jnp.float32), **kw)
    op.open(RuntimeContext())
    return op


def run_paged(batches, window_ms: int, checkpoint_every: int, cap: int,
              pipeline_depth: int = 1, native_shards: int = 0):
    """One full paged pass (device tier, K_cap = ``cap``): the cold-key
    paging subsystem's cost + occupancy on the headline workload.  Returns
    (records/sec, paging stats, phase dict)."""
    from flink_tpu.core.batch import RecordBatch, Watermark

    op = _build_op(window_ms, paging_cap=cap, pipeline_depth=pipeline_depth,
                   native_shards=native_shards)
    t0 = time.perf_counter()
    n = 0
    for i, (keys, vals, ts) in enumerate(batches):
        out = op.process_batch(RecordBatch({"k": keys, "v": vals},
                                           timestamps=ts))
        out += op.process_watermark(Watermark(int(ts.max()) - 1))
        n += len(keys)
        if checkpoint_every and (i + 1) % checkpoint_every == 0:
            op.prepare_snapshot_pre_barrier()
            op.snapshot_state()
    stats = dict(op.paging_stats())   # occupancy BEFORE end-of-input drains
    tail = op.end_input()
    if tail:
        np.asarray(tail[-1].column("result"))
    elapsed = time.perf_counter() - t0
    stats["evictions"] = op.paging_stats()["evictions"]
    stats["promotions"] = op.paging_stats()["promotions"]
    return n / elapsed, stats, dict(op.phase_ns)


def _fire_digests(elements):
    """(window_start, rows, sum(result)) per fired batch — the equivalence
    fingerprint for restore+replay checks."""
    out = []
    for b in elements:
        if hasattr(b, "columns") and "result" in b.columns:
            out.append((int(np.asarray(b.column("window_start"))[0]),
                        len(b),
                        float(np.asarray(b.column("result"),
                                         np.float64).sum())))
    return out


def run_tpu_native(batches, window_ms: int, checkpoint_every: int,
                   emit_tier: str = "host", device_sync: str = "auto",
                   timed_passes: int = 3, pipeline_depth: int = 1,
                   native_shards: int = 0, mesh_devices: int = 0,
                   key_capacity: int = 1 << 20):
    """Timed checkpointable run.  Returns (records/sec, windows fired,
    snapshots taken, phase dict, mid-run snapshot + its batch index +
    post-checkpoint digests for the replay check)."""
    from flink_tpu.core.batch import RecordBatch, Watermark
    from flink_tpu.observability import tracing

    def run(op, subset, checkpoint_every=0):
        t0 = time.perf_counter()
        n = 0
        fired = 0
        snaps = 0
        mid = None
        digests = []
        snap_ns = 0
        for i, (keys, vals, ts) in enumerate(subset):
            out = op.process_batch(RecordBatch({"k": keys, "v": vals},
                                               timestamps=ts))
            out += op.process_watermark(Watermark(int(ts.max()) - 1))
            fired += sum(len(b) for b in out)
            if mid is not None:
                digests.extend(_fire_digests(out))
            n += len(keys)
            if checkpoint_every and (i + 1) % checkpoint_every == 0:
                # checkpoint lifecycle spans (no-ops unless a span journal
                # is installed — the --trace leg): trigger → snapshot →
                # complete on the same timeline as the hot-stage phases
                cid = snaps + 1
                tracing.instant("checkpoint.trigger", cat="checkpoint",
                                checkpoint=cid)
                s0 = time.perf_counter_ns()
                with tracing.span("checkpoint.snapshot", cat="checkpoint",
                                  checkpoint=cid):
                    op.prepare_snapshot_pre_barrier()
                    snap = op.snapshot_state()
                s1 = time.perf_counter_ns()
                tracing.complete("checkpoint", s0, s1, cat="checkpoint",
                                 checkpoint=cid)
                snap_ns += s1 - s0
                snaps += 1
                if mid is None:          # keep the FIRST mid-run snapshot
                    mid = (i, snap)
        tail = op.end_input()
        fired += sum(len(b) for b in tail)
        if mid is not None:
            digests.extend(_fire_digests(tail))
        if tail:
            np.asarray(tail[-1].column("result"))  # block until ready
        elapsed = time.perf_counter() - t0
        # capture THIS pass's phase accounting (reset_state clears it), so
        # the reported breakdown always belongs to the winning pass
        phases = dict(op.phase_ns)
        phases["snapshot_total"] = snap_ns
        phases["elapsed"] = int(elapsed * 1e9)
        shard_ns = {k: [int(x) for x in v.tolist()]
                    for k, v in op.phase_shard_ns.items()}
        return (n / elapsed, fired, snaps, mid, digests,
                phases, dict(op.phase_bytes), shard_ns)

    # warmup: cover the full key-capacity ladder so the timed run never
    # compiles — one synthetic pass inserts every key, then real batches.
    # The SAME operator instance is reused (jit caches key on the instance);
    # reset_state() drops data but keeps compiled steps.
    nk = 1 + int(max(b[0].max() for b in batches))
    bsz = len(batches[0][0])
    allkeys = np.arange(nk, dtype=np.int64)
    warm = [(allkeys[lo:lo + bsz],
             np.zeros(min(bsz, nk - lo), np.float32),
             np.zeros(min(bsz, nk - lo), np.int64))
            for lo in range(0, nk, bsz)]
    op = _build_op(window_ms, emit_tier, device_sync,
                   pipeline_depth=pipeline_depth, native_shards=native_shards,
                   mesh_devices=mesh_devices, key_capacity=key_capacity)
    run(op, warm + batches[:2] + batches[-1:])
    # best of three timed passes: a shared host suffers EPISODIC
    # multi-second slowdowns (measured ±70% swings on otherwise-stable C
    # kernels) — every pass is a complete, honest run
    # with the SAME checkpoint cadence, and the baselines get the same
    # best-of treatment below.  GC is paused inside the timed region
    # (bench hygiene; re-enabled after).
    import gc
    best = None
    for _ in range(timed_passes):
        op.reset_state()
        gc.disable()
        try:
            res = run(op, batches, checkpoint_every)
        finally:
            gc.enable()
        if best is None or res[0] > best[0]:
            best = res
    rps, fired, snaps, mid, digests, phases, bytes_, shard_ns = best
    return (rps, fired, snaps, mid, digests, phases, bytes_, shard_ns, op)


def replay_check(batches, window_ms: int, mid, digests,
                 emit_tier: str = "host", device_sync: str = "auto",
                 pipeline_depth: int = 1, native_shards: int = 0,
                 mesh_devices: int = 0, key_capacity: int = 1 << 20) -> bool:
    """Exactly-once evidence: restore the mid-run snapshot into a FRESH
    operator, replay the remaining batches, and require the identical
    per-window fire digests."""
    if mid is None:
        return True
    from flink_tpu.core.batch import RecordBatch, Watermark

    i, snap = mid
    op = _build_op(window_ms, emit_tier, device_sync,
                   pipeline_depth=pipeline_depth, native_shards=native_shards,
                   mesh_devices=mesh_devices, key_capacity=key_capacity)
    op.restore_state(snap)
    out = []
    for keys, vals, ts in batches[i + 1:]:
        out += op.process_batch(RecordBatch({"k": keys, "v": vals},
                                            timestamps=ts))
        out += op.process_watermark(Watermark(int(ts.max()) - 1))
    out += op.end_input()
    got = _fire_digests(out)
    if len(got) != len(digests):
        return False
    for (w1, n1, s1), (w2, n2, s2) in zip(got, digests):
        if w1 != w2 or n1 != n2 or abs(s1 - s2) > 1e-6 * max(abs(s2), 1.0):
            return False
    return True


def measure_fire_latency(batches, window_ms: int,
                         min_samples: int = 128,
                         max_samples: int = 256,
                         emit_tier: str = "host",
                         device_sync: str = "auto",
                         pipeline_depth: int = 1,
                         native_shards: int = 0) -> dict:
    """Window-fire latency: watermark arrival -> fired rows materialized on
    the host.  >= ``min_samples`` samples (VERDICT r2 weak #2), capped at
    ``max_samples`` (each device-tier sample is a real synchronous
    download); each cycle fills one full window then fires it.  Returns
    p50/p95/p99 ms."""
    from flink_tpu.core.batch import RecordBatch, Watermark

    rng = np.random.default_rng(3)
    # split batches into half-batches until there are enough fire cycles
    cycles = list(batches)
    while len(cycles) < min_samples:
        halved = []
        for keys, vals, ts in cycles:
            h = len(keys) // 2
            if h == 0:
                halved.append((keys, vals, ts))
                continue
            halved.append((keys[:h], vals[:h], ts[:h]))
            halved.append((keys[h:], vals[h:], ts[h:]))
        if len(halved) == len(cycles):
            break
        cycles = halved
    cycles = cycles[:max_samples]
    op = _build_op(window_ms, emit_tier, device_sync,
                   pipeline_depth=pipeline_depth, native_shards=native_shards)
    # warm compiles/allocations outside the timed samples
    warm_keys = batches[0][0]
    for i in range(2):
        wts = np.sort(rng.integers(0, window_ms, len(warm_keys))).astype(
            np.int64) + i * window_ms
        op.process_batch(RecordBatch(
            {"k": warm_keys, "v": np.ones(len(warm_keys), np.float32)},
            timestamps=wts))
        op.process_watermark(Watermark((i + 1) * window_ms - 1))
    op.reset_state()
    lats = []
    for i, (keys, vals, _ts) in enumerate(cycles):
        # re-time: one full window per cycle, so every watermark fires
        ts = i * window_ms + np.sort(
            rng.integers(0, window_ms, len(keys))).astype(np.int64)
        op.process_batch(RecordBatch({"k": keys, "v": vals}, timestamps=ts))
        t0 = time.perf_counter()
        out = op.process_watermark(Watermark((i + 1) * window_ms - 1))
        if out:
            np.asarray(out[-1].column("result"))  # block until on host
            lats.append(time.perf_counter() - t0)
    if not lats:
        return {"p50": 0.0, "p95": 0.0, "p99": 0.0, "samples": 0}
    ms = np.asarray(lats) * 1000.0
    return {"p50": float(np.percentile(ms, 50)),
            "p95": float(np.percentile(ms, 95)),
            "p99": float(np.percentile(ms, 99)),
            "samples": int(ms.size)}


def _gc_paused(fn):
    """Same GC treatment as the TPU timed passes (methodology symmetry)."""
    import functools
    import gc

    @functools.wraps(fn)
    def wrapped(*a, **kw):
        gc.disable()
        try:
            return fn(*a, **kw)
        finally:
            gc.enable()
    return wrapped


@_gc_paused
def run_heap_baseline(batches, window_ms: int, budget_s: float = 30.0):
    """Single-node per-record Python dict loop — the HeapStateBackend /
    CopyOnWriteStateMap analog (reference hot loop, SURVEY §3.3(c))."""
    state = {}
    fired = 0
    t0 = time.perf_counter()
    n = 0
    for keys, vals, ts in batches:
        kl = keys.tolist()
        vl = vals.tolist()
        tl = ts.tolist()
        for k, v, t in zip(kl, vl, tl):
            w = t // window_ms
            sk = (k, w)
            acc = state.get(sk)
            state[sk] = v if acc is None else acc + v
        # watermark: fire windows whose end passed (emit + evict)
        wm = tl[-1] - 1
        done = [sk for sk in state if (sk[1] + 1) * window_ms - 1 <= wm]
        for sk in done:
            state.pop(sk)
            fired += 1
        n += len(kl)
        if time.perf_counter() - t0 > budget_s:
            break
    elapsed = time.perf_counter() - t0
    return n / elapsed, fired


@_gc_paused
def run_numpy_baseline(batches, window_ms: int):
    """Competent vectorized CPU contender: C++ hash key index (fair — the
    reference's heap backend is compiled Java), one bincount per
    (batch, pane), vectorized fires.  Single core."""
    from flink_tpu.state.keyindex import make_key_index

    index = None
    panes: dict = {}          # pane -> float64[cap] sums
    counts: dict = {}         # pane -> int64[cap]
    cap = 1 << 20
    fired = 0
    t0 = time.perf_counter()
    n = 0
    for keys, vals, ts in batches:
        if index is None:
            index = make_key_index(keys[0])
        slots = index.lookup_or_insert(keys)
        while index.num_keys > cap:
            cap <<= 1
        pane = ts // window_ms
        for p in np.unique(pane).tolist():
            m = pane == p
            s = slots[m] if not m.all() else slots
            v = vals[m] if not m.all() else vals
            arr = panes.get(p)
            if arr is None or arr.size < cap:
                grown = np.zeros(cap, np.float64)
                cnt = np.zeros(cap, np.int64)
                if arr is not None:
                    grown[:arr.size] = arr
                    cnt[:arr.size] = counts[p]
                panes[p], counts[p] = arr, cnt = grown, cnt
            panes[p] += np.bincount(s, weights=v, minlength=cap)
            counts[p] += np.bincount(s, minlength=cap)
        # fire windows whose end passed
        wm = int(ts.max()) - 1
        done = [p for p in panes if (p + 1) * window_ms - 1 <= wm]
        for p in sorted(done):
            nz = np.flatnonzero(counts[p][:index.num_keys] > 0)
            if nz.size:
                _result = panes[p][nz]              # emitted values
                _keys = np.asarray(index.reverse_keys())[nz]
                fired += nz.size
            del panes[p], counts[p]
        n += len(keys)
    # end of input: flush
    for p in sorted(panes):
        nz = np.flatnonzero(counts[p][:index.num_keys] > 0)
        fired += int(nz.size)
    elapsed = time.perf_counter() - t0
    return n / elapsed, fired


# ---------------------------------------------------------------------------
# BASELINE.md configs 1/3/4/5 (config 2 — the 1M-key tumbling sum — is the
# headline path below; these run via --config N)
# ---------------------------------------------------------------------------


def _best_of(fn, passes: int):
    """Best-of-N timed passes with GC paused (same methodology as the
    headline run; this host shows episodic multi-second slowdowns)."""
    import gc
    best = None
    for _ in range(passes):
        gc.disable()
        try:
            res = fn()
        finally:
            gc.enable()
        if best is None or res[0] > best[0]:
            best = res
    return best


def _drain(op, batches, key_col="k"):
    """Feed (cols, ts) batches through an operator with per-batch
    watermarks; returns (records, fired rows, elapsed_s)."""
    from flink_tpu.core.batch import RecordBatch, Watermark

    t0 = time.perf_counter()
    n = 0
    fired = 0
    for cols, ts in batches:
        out = op.process_batch(RecordBatch(cols, timestamps=ts))
        out += op.process_watermark(Watermark(int(ts.max()) - 1))
        fired += sum(len(b) for b in out if hasattr(b, "columns"))
        n += len(ts)
    tail = op.end_input()
    fired += sum(len(b) for b in tail if hasattr(b, "columns"))
    if tail and hasattr(tail[-1], "columns"):
        cols = tail[-1].columns
        np.asarray(next(iter(cols.values())))   # block until on host
    return n, fired, time.perf_counter() - t0


def _result(cfg: int, metric: str, rps: float, heap_rps: float,
            extra: dict) -> dict:
    return {
        "metric": metric,
        "value": round(rps, 1),
        "unit": "records/sec",
        "config": cfg,
        "vs_baseline": round(rps / heap_rps, 3),
        "details": {"heap_baseline_rps": round(heap_rps, 1), **extra},
    }


# ---- config 1: socket-style WordCount (Tumbling 5s count per word) --------

def _make_lines(n_words: int, vocab: int, seed: int = 11):
    """Text lines (10 words each), Zipf word frequencies — the
    SocketWindowWordCount input shape.  Returns [(lines, ts_ms)]."""
    rng = np.random.default_rng(seed)
    words = np.asarray([f"w{i:05d}" for i in range(vocab)], object)
    ranks = rng.zipf(1.3, n_words).astype(np.int64) % vocab
    flat = words[ranks]
    per_line = 10
    lines = [" ".join(flat[i:i + per_line])
             for i in range(0, n_words, per_line)]
    batches = []
    bsz = 4096                       # lines per batch (~41k words)
    t = 0
    for lo in range(0, len(lines), bsz):
        chunk = lines[lo:lo + bsz]
        ts = t + np.sort(rng.integers(0, 1000, len(chunk))).astype(np.int64)
        t += 1000
        batches.append((chunk, ts))
    return batches


def run_config1(smoke: bool) -> dict:
    """WordCount: tokenize lines (the flatMap), keyBy(word),
    Tumbling(5s) count — ``SocketWindowWordCount.java:69-84``.  The socket
    is not benchmarked (that would measure the kernel's TCP stack);
    tokenization IS in the timed region on both sides."""
    import jax.numpy as jnp
    from flink_tpu.core.batch import RecordBatch, Watermark
    from flink_tpu.core.functions import RuntimeContext, SumAggregator
    from flink_tpu.operators.window_agg import WindowAggOperator
    from flink_tpu.windowing.assigners import TumblingEventTimeWindows

    n_words = 1 << 17 if smoke else 1 << 22
    batches = _make_lines(n_words, vocab=30_000)

    def tokenize(chunk, ts):
        words, wts = [], []
        for line, t in zip(chunk, ts.tolist()):
            ws = line.split()
            words.extend(ws)
            wts.extend([t] * len(ws))
        return (np.asarray(words, object),
                np.ones(len(words), np.float32),
                np.asarray(wts, np.int64))

    def mk_op():
        op = WindowAggOperator(
            TumblingEventTimeWindows.of(5000), SumAggregator(jnp.float32),
            key_column="k", value_column="v", emit_tier="host",
            snapshot_source="mirror", device_sync="auto")
        op.open(RuntimeContext())
        return op

    op = mk_op()
    for chunk, ts in batches[:2]:            # warm compiles
        k, v, wts = tokenize(chunk, ts)
        op.process_batch(RecordBatch({"k": k, "v": v}, timestamps=wts))
    op.reset_state()

    def tpu_pass():
        op.reset_state()
        t0 = time.perf_counter()
        n = fired = 0
        for chunk, ts in batches:
            k, v, wts = tokenize(chunk, ts)
            out = op.process_batch(RecordBatch({"k": k, "v": v},
                                               timestamps=wts))
            out += op.process_watermark(Watermark(int(wts[-1]) - 1))
            fired += sum(len(b) for b in out if hasattr(b, "columns"))
            n += len(k)
        tail = op.end_input()
        fired += sum(len(b) for b in tail if hasattr(b, "columns"))
        return n / (time.perf_counter() - t0), fired

    rps, fired = _best_of(tpu_pass, 2 if smoke else 3)

    def heap_pass():
        state = {}
        t0 = time.perf_counter()
        n = fired = 0
        for chunk, ts in batches:
            tl = ts.tolist()
            for line, t in zip(chunk, tl):
                for w in line.split():
                    sk = (w, t // 5000)
                    state[sk] = state.get(sk, 0) + 1
                    n += 1
            wm = tl[-1] - 1
            done = [sk for sk in state if (sk[1] + 1) * 5000 - 1 <= wm]
            for sk in done:
                state.pop(sk)
                fired += 1
            if time.perf_counter() - t0 > (3.0 if smoke else 20.0):
                break
        return n / (time.perf_counter() - t0), fired

    heap_rps, _hf = _best_of(heap_pass, 2)
    return _result(
        1, "records/sec/chip (WordCount words, Tumbling 5s count)",
        rps, heap_rps, {"windows_fired": fired, "n_words": n_words,
                        "tokenize_in_timed_region": True})


# ---- config 3: Sliding(60s, 5s) multi-field aggregate ---------------------

def run_config3(smoke: bool) -> dict:
    """Sliding(60s,5s) multi-field AggregateFunction (sum/count/min/max →
    avg): the pane-combine shape of ``HeapWindowsGrouping.java``; the heap
    baseline is the reference ``WindowOperator`` per-record behavior — each
    element updates all 12 covering windows."""
    import jax.numpy as jnp
    from flink_tpu.core.functions import (CountAggregator, MaxAggregator,
                                          MinAggregator, RuntimeContext,
                                          SumAggregator, TupleAggregator)
    from flink_tpu.operators.window_agg import WindowAggOperator
    from flink_tpu.windowing.assigners import SlidingEventTimeWindows

    n = 1 << 17 if smoke else 1 << 23
    n_keys = 100_000
    rng = np.random.default_rng(13)
    batches = []
    t = 0
    bsz = 1 << 17
    for lo in range(0, n, bsz):
        b = min(bsz, n - lo)
        keys = rng.integers(0, n_keys, b).astype(np.int64)
        vals = rng.random(b).astype(np.float32)
        ts = t + np.sort(rng.integers(0, 5000, b)).astype(np.int64)
        t += 5000
        batches.append(({"k": keys, "v": vals}, ts))

    def mk_agg():
        return TupleAggregator({
            "total": ("v", SumAggregator(jnp.float32)),
            "n": ("v", CountAggregator()),
            "lo": ("v", MinAggregator(jnp.float32)),
            "hi": ("v", MaxAggregator(jnp.float32)),
        })

    op = WindowAggOperator(
        SlidingEventTimeWindows.of(60_000, 5_000), mk_agg(),
        key_column="k", value_selector=lambda c: c,
        emit_tier="host", snapshot_source="mirror", device_sync="auto")
    op.open(RuntimeContext())
    _drain(op, batches[:2])                  # warm compiles

    def tpu_pass():
        op.reset_state()
        nn, fired, el = _drain(op, batches)
        return nn / el, fired

    rps, fired = _best_of(tpu_pass, 2 if smoke else 3)

    def heap_pass():
        state = {}
        t0 = time.perf_counter()
        nn = fired = 0
        for cols, ts in batches:
            kl = cols["k"].tolist()
            vl = cols["v"].tolist()
            tl = ts.tolist()
            for k, v, tt in zip(kl, vl, tl):
                # every element joins the 12 sliding windows covering it
                last = tt // 5000
                for w in range(max(0, last - 11), last + 1):
                    sk = (k, w)
                    acc = state.get(sk)
                    if acc is None:
                        state[sk] = [v, 1, v, v]
                    else:
                        acc[0] += v
                        acc[1] += 1
                        if v < acc[2]:
                            acc[2] = v
                        if v > acc[3]:
                            acc[3] = v
                nn += 1
            wm = tl[-1] - 1
            done = [sk for sk in state
                    if sk[1] * 5000 + 60_000 - 1 <= wm]
            for sk in done:
                state.pop(sk)
                fired += 1
            if time.perf_counter() - t0 > (3.0 if smoke else 20.0):
                break
        return nn / (time.perf_counter() - t0), fired

    heap_rps, _hf = _best_of(heap_pass, 2)
    return _result(
        3, "records/sec/chip (Sliding 60s/5s multi-field sum/count/min/max)",
        rps, heap_rps, {"windows_fired": fired, "n_records": n,
                        "n_keys": n_keys})


# ---- config 4: session windows + Zipf keys --------------------------------

def run_config4(smoke: bool) -> dict:
    """Session windows (gap merge) under Zipf key skew —
    ``MergingWindowSet.java`` / ``WindowOperator.java:311-411``."""
    import jax.numpy as jnp
    from flink_tpu.core.functions import RuntimeContext, SumAggregator
    from flink_tpu.operators.session_window import SessionWindowOperator
    from flink_tpu.windowing.assigners import EventTimeSessionWindows

    n = 1 << 16 if smoke else 1 << 21
    n_keys = 100_000
    gap = 1000
    rng = np.random.default_rng(17)
    batches = []
    t = 0
    bsz = 1 << 15
    for lo in range(0, n, bsz):
        b = min(bsz, n - lo)
        keys = (rng.zipf(1.3, b).astype(np.int64) - 1) % n_keys
        vals = rng.random(b).astype(np.float32)
        # bursts with inter-burst silence > gap, so sessions CLOSE
        ts = t + np.sort(rng.integers(0, 800, b)).astype(np.int64)
        t += 3000
        batches.append(({"k": keys, "v": vals}, ts))

    def mk_op():
        op = SessionWindowOperator(
            EventTimeSessionWindows(gap), SumAggregator(jnp.float32),
            key_column="k", value_column="v")
        op.open(RuntimeContext())
        return op

    op = mk_op()
    _drain(op, batches[:2])                  # warm compiles

    def tpu_pass():
        o = mk_op()                          # session op: fresh state
        nn, fired, el = _drain(o, batches)
        return nn / el, fired

    rps, fired = _best_of(tpu_pass, 2 if smoke else 3)

    def heap_pass():
        # MergingWindowSet analog: per key a list of (start, end, acc)
        sessions: dict = {}
        t0 = time.perf_counter()
        nn = fired = 0
        for cols, ts in batches:
            kl = cols["k"].tolist()
            vl = cols["v"].tolist()
            tl = ts.tolist()
            for k, v, tt in zip(kl, vl, tl):
                lst = sessions.setdefault(k, [])
                new = [tt, tt + gap, v]
                merged = []
                for s in lst:
                    if s[0] <= new[1] and new[0] <= s[1]:  # overlap: merge
                        new = [min(s[0], new[0]), max(s[1], new[1]),
                               s[2] + new[2]]
                    else:
                        merged.append(s)
                merged.append(new)
                sessions[k] = merged
                nn += 1
            wm = tl[-1] - 1
            for k in list(sessions):
                keep = []
                for s in sessions[k]:
                    if s[1] - 1 <= wm:
                        fired += 1
                    else:
                        keep.append(s)
                if keep:
                    sessions[k] = keep
                else:
                    del sessions[k]
            if time.perf_counter() - t0 > (3.0 if smoke else 20.0):
                break
        return nn / (time.perf_counter() - t0), fired

    heap_rps, _hf = _best_of(heap_pass, 2)
    return _result(
        4, "records/sec/chip (session windows gap=1s, Zipf keys)",
        rps, heap_rps, {"sessions_fired": fired, "n_records": n,
                        "gap_ms": gap})


# ---- config 5: SQL TUMBLE/HOP over a lineitem stream ----------------------

def _lineitem(n: int, seed: int = 19):
    rng = np.random.default_rng(seed)
    flags = np.asarray(["A", "N", "R"], object)
    return {
        "l_returnflag": flags[rng.integers(0, 3, n)],
        "l_quantity": rng.integers(1, 51, n).astype(np.float64),
        "l_extendedprice": (rng.random(n) * 1000).astype(np.float64),
        "l_discount": (rng.random(n) * 0.1).astype(np.float64),
        "ts": np.sort(rng.integers(0, 120_000, n)).astype(np.int64),
    }


def run_config5(smoke: bool) -> dict:
    """SQL TUMBLE and HOP GroupWindowAggregate over a TPC-H-like lineitem
    stream — ``StreamExecGroupWindowAggregate.java:103``.  Timed region =
    plan + execute + collect (the whole executeSql path)."""
    from flink_tpu.sql.table_env import TableEnvironment

    n = 1 << 16 if smoke else 1 << 22
    cols = _lineitem(n)
    tumble_sql = (
        "SELECT l_returnflag, "
        "TUMBLE_START(ts, INTERVAL '5' SECOND) AS ws, "
        "SUM(l_extendedprice * (1 - l_discount)) AS revenue, "
        "SUM(l_quantity) AS qty, COUNT(*) AS n FROM lineitem "
        "GROUP BY l_returnflag, TUMBLE(ts, INTERVAL '5' SECOND)")
    hop_sql = (
        "SELECT l_returnflag, "
        "HOP_START(ts, INTERVAL '5' SECOND, INTERVAL '60' SECOND) AS ws, "
        "SUM(l_extendedprice * (1 - l_discount)) AS revenue, "
        "COUNT(*) AS n FROM lineitem "
        "GROUP BY l_returnflag, "
        "HOP(ts, INTERVAL '5' SECOND, INTERVAL '60' SECOND)")

    def sql_pass(sql):
        def run():
            tenv = TableEnvironment()
            tenv.register_collection("lineitem", columns=cols,
                                     rowtime="ts", batch_size=1 << 17)
            t0 = time.perf_counter()
            rows = tenv.execute_sql(sql).collect()
            return n / (time.perf_counter() - t0), len(rows)
        return run

    warm = sql_pass(tumble_sql)()            # warm compiles
    t_rps, t_rows = _best_of(sql_pass(tumble_sql), 2 if smoke else 3)
    h_rps, h_rows = _best_of(sql_pass(hop_sql), 1 if smoke else 2)

    def heap_pass():
        state: dict = {}
        t0 = time.perf_counter()
        fl = cols["l_returnflag"].tolist()
        qty = cols["l_quantity"].tolist()
        price = cols["l_extendedprice"].tolist()
        disc = cols["l_discount"].tolist()
        tl = cols["ts"].tolist()
        nn = 0
        for f, q, p, d, tt in zip(fl, qty, price, disc, tl):
            sk = (f, tt // 5000)
            acc = state.get(sk)
            rev = p * (1 - d)
            if acc is None:
                state[sk] = [rev, q, 1]
            else:
                acc[0] += rev
                acc[1] += q
                acc[2] += 1
            nn += 1
            if nn % 65536 == 0 and \
                    time.perf_counter() - t0 > (3.0 if smoke else 20.0):
                break
        return nn / (time.perf_counter() - t0), len(state)

    heap_rps, _groups = _best_of(heap_pass, 2)
    return _result(
        5, "records/sec/chip (SQL TUMBLE 5s lineitem revenue aggregate)",
        t_rps, heap_rps,
        {"tumble_result_rows": t_rows, "hop_rps": round(h_rps, 1),
         "hop_result_rows": h_rows, "n_records": n,
         "warmup_rps": round(warm[0], 1)})


CONFIG_RUNNERS = {1: run_config1, 3: run_config3, 4: run_config4,
                  5: run_config5}


def run_wedge_smoke(window_ms: int = 1000) -> dict:
    """``--inject-wedge``: exercise the SHARED runtime/bench recovery path
    end-to-end on CPU-sized traffic.  A deterministic ``WedgedDevice``
    chaos schedule hangs the Nth hot-path dispatch; the watchdog must
    quarantine, the operator must degrade to the host tier mid-stream
    without dropping records, a snapshot must complete DURING quarantine,
    the healer must heal once the schedule does, and the operator must
    re-promote at the next checkpoint-aligned safe point — with fire
    digests identical to an unfaulted pass."""
    import jax.numpy as jnp

    from flink_tpu.core.batch import RecordBatch, Watermark
    from flink_tpu.core.functions import RuntimeContext, SumAggregator
    from flink_tpu.operators.window_agg import WindowAggOperator
    from flink_tpu.runtime import device_health as dh
    from flink_tpu.testing import chaos
    from flink_tpu.windowing.assigners import TumblingEventTimeWindows

    def build():
        op = WindowAggOperator(
            TumblingEventTimeWindows.of(window_ms),
            SumAggregator(jnp.float32), key_column="k", value_column="v",
            emit_tier="device")
        op.open(RuntimeContext())
        return op

    rng = np.random.default_rng(7)
    batches = []
    for i in range(24):
        k = rng.integers(0, 64, 512)
        v = np.ones(512, np.float32)
        ts = i * (window_ms // 2) + np.sort(
            rng.integers(0, window_ms // 2, 512)).astype(np.int64)
        batches.append((k, v, ts))

    def digests(els):
        out = []
        for b in els:
            if hasattr(b, "columns") and "result" in b.columns:
                out.append((int(np.asarray(b.column("window_start"))[0]),
                            len(b),
                            float(np.asarray(b.column("result"),
                                             np.float64).sum())))
        return out

    def one_pass(inject: bool):
        prev = dh.get_monitor(create=False)
        dh.set_monitor(dh.DeviceHealthMonitor(
            dh.WatchdogConfig(deadline_floor_s=0.5), heal_async=False))
        inj = chaos.FaultInjector(seed=3)
        sched = (inj.inject("device.dispatch", chaos.WedgedDevice(at=8))
                 if inject else None)
        op = build()
        out = []
        snapshotted_degraded = False
        try:
            with chaos.installed(inj):
                for i, (k, v, ts) in enumerate(batches):
                    out += op.process_batch(
                        RecordBatch({"k": k, "v": v}, timestamps=ts))
                    out += op.process_watermark(Watermark(int(ts.max()) - 1))
                    if inject and i == 12:
                        op.prepare_snapshot_pre_barrier()
                        op.snapshot_state()   # checkpoint DURING quarantine
                        snapshotted_degraded = op._degraded
                        sched.heal()
                        dh.get_monitor().probe_now()
                    if inject and i == 16:
                        out += op.prepare_snapshot_pre_barrier()  # repromote
                out += op.end_input()
            stats = op.device_health_stats()
            mon = dh.get_monitor().status()
            op.close()
        finally:
            dh.set_monitor(prev)
        return digests(out), stats, mon, snapshotted_degraded

    clean, _s, _m, _d = one_pass(False)
    wedged, stats, mon, snap_degraded = one_pass(True)
    ok = (clean == wedged and mon["quarantines"] == 1 and mon["heals"] == 1
          and stats["quarantine_migrations"] == 1
          and stats["repromotions"] == 1 and stats["degraded"] == 0
          and snap_degraded)
    return {"metric": "inject-wedge recovery smoke", "ok": ok,
            "digest_match": clean == wedged,
            "snapshot_during_quarantine": snap_degraded,
            "device_health": {**{k: mon[k] for k in
                                 ("state", "quarantines", "heals",
                                  "watchdog_timeouts")}, **stats}}


def run_checkpoint_backpressure(interval_ms: int, budget_ms: float,
                                min_completed: int = 1,
                                n_records: int = 40_000) -> dict:
    """``--checkpoint-interval``: checkpoint duration + persisted in-flight
    bytes under INJECTED backpressure (ISSUE-5 CI satellite).  A seeded
    ``SlowConsumer`` schedule stalls one source's channels into the keyed
    window subtasks (bursty drain stalls — input queues deepen, barriers
    crawl behind the backlog) while a ``SlowDisk`` schedule stalls the
    checkpoint store; the job runs with aligned-with-timeout escalation,
    so checkpoints must keep completing within ``budget_ms`` regardless —
    the unaligned-checkpoint acceptance in bench form."""
    from flink_tpu.datastream.api import StreamExecutionEnvironment
    from flink_tpu.runtime.checkpoint.storage import InMemoryCheckpointStorage
    from flink_tpu.testing import chaos
    from flink_tpu.windowing.assigners import TumblingEventTimeWindows

    rng = np.random.default_rng(11)
    keys = rng.integers(0, 101, n_records)
    vals = np.ones(n_records, np.float64)
    ts = np.sort(rng.integers(0, 4000, n_records))
    env = StreamExecutionEnvironment()
    env.set_parallelism(2)
    sink = (env.from_collection(columns={"k": keys, "v": vals, "t": ts},
                                batch_size=256)
            .assign_timestamps_and_watermarks(0, timestamp_column="t")
            .key_by("k")
            .window(TumblingEventTimeWindows.of(1000))
            .sum("v").collect())
    inj = chaos.FaultInjector(seed=29)
    inj.inject("channel.recv",
               chaos.SlowConsumer(max_s=0.03, min_s=0.015, p=0.3, burst=30,
                                  channel="[0]->"))
    inj.inject("checkpoint.store",
               chaos.SlowDisk(max_s=0.04, min_s=0.01, p=0.5, times=30))
    storage = InMemoryCheckpointStorage(retain=5)
    t0 = time.monotonic()
    with chaos.installed(inj):
        res = env.execute_cluster(
            storage=storage, checkpoint_interval_ms=interval_ms,
            checkpoint_timeout_s=max(2.0, budget_ms / 1000.0),
            alignment_timeout_ms=100, tolerable_failed_checkpoints=-1,
            timeout_s=300)
    wall_ms = (time.monotonic() - t0) * 1000.0
    status = env._last_cluster.job_status()
    stats = status["checkpoint_stats"]
    durations = [s["duration_ms"] for s in stats]
    persisted = [s["persisted_inflight_bytes"] for s in stats]
    completed = len(res.completed_checkpoints)
    unaligned = sum(1 for s in stats if s["unaligned"])
    rows = sum(float(r["v"]) for r in sink.rows())
    exactly_once = abs(rows - float(vals.sum())) < 0.5
    ok = (res.state == "FINISHED" and completed >= min_completed
          and exactly_once and durations
          and max(durations) <= budget_ms)
    return {
        "metric": "checkpoint duration under injected backpressure",
        "ok": ok,
        "state": res.state,
        "exactly_once": exactly_once,
        "completed_checkpoints": completed,
        "unaligned_checkpoints": unaligned,
        "failed_checkpoints": status["checkpoints"]["failed_checkpoints"],
        "checkpoint_interval_ms": interval_ms,
        "budget_ms": budget_ms,
        "max_duration_ms": max(durations) if durations else None,
        "mean_duration_ms": (round(sum(durations) / len(durations), 1)
                             if durations else None),
        "max_alignment_ms": max((s["alignment_ms"] for s in stats),
                                default=0.0),
        "persisted_inflight_bytes_total": int(sum(persisted)),
        "persisted_inflight_bytes_max": int(max(persisted, default=0)),
        "overtaken_bytes_total": int(sum(s["overtaken_bytes"]
                                         for s in stats)),
        "wall_ms": round(wall_ms, 1),
    }


def _tree_eq(a, b) -> bool:
    """Bit-exact structural equality of two snapshot trees (bool form of
    the test suite's assertion helper — the bench must report, not raise)."""
    if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        a, b = np.asarray(a), np.asarray(b)
        return (a.dtype == b.dtype and a.shape == b.shape
                and bool(np.array_equal(a, b)))
    if isinstance(a, dict):
        return (isinstance(b, dict) and a.keys() == b.keys()
                and all(_tree_eq(a[k], b[k]) for k in a))
    if isinstance(a, (list, tuple)):
        return (isinstance(b, (list, tuple)) and len(a) == len(b)
                and all(_tree_eq(x, y) for x, y in zip(a, b)))
    return bool(a == b)


def run_incremental_checkpoint_bench(smoke: bool = False,
                                     churn_frac: float = 0.10,
                                     rounds: int = 5) -> dict:
    """``--checkpoint-interval`` incremental leg (ISSUE-16): at a steady
    state where ``churn_frac`` of the keys change per interval, measure
    bytes/checkpoint for delta cuts vs the full dense snapshot, the
    increments-per-base chain depth in ``IncrementalCheckpointStorage``,
    and the measured recovery time (chain resolve + operator restore).
    The chain-restored state must be digest-identical to the full
    snapshot — reported as ``digest_match`` and gated unconditionally by
    ``check_incremental_budget``."""
    import shutil
    import tempfile

    import jax.numpy as jnp

    from flink_tpu.core.batch import RecordBatch
    from flink_tpu.core.functions import RuntimeContext, SumAggregator
    from flink_tpu.operators.base import snapshot_scope
    from flink_tpu.operators.window_agg import WindowAggOperator
    from flink_tpu.runtime.checkpoint import delta
    from flink_tpu.runtime.checkpoint.incremental import \
        IncrementalCheckpointStorage
    from flink_tpu.windowing.assigners import TumblingEventTimeWindows

    n_keys = 50_000 if smoke else 1_000_000
    churn = max(1, int(n_keys * churn_frac))
    rng = np.random.default_rng(17)
    op = WindowAggOperator(TumblingEventTimeWindows.of(1000),
                           SumAggregator(jnp.float32),
                           key_column="k", value_column="v")
    op.open(RuntimeContext())
    op.incremental_state = True

    def feed(keys):
        op.process_batch(RecordBatch(
            {"k": keys, "v": np.ones(keys.size, np.float32)},
            timestamps=np.full(keys.size, 100, np.int64)))

    tmp = tempfile.mkdtemp(prefix="bench-incr-")
    try:
        storage = IncrementalCheckpointStorage(
            tmp, retain=rounds + 2, max_increments_per_base=rounds + 2,
            compact_in_background=False)
        for part in np.array_split(np.arange(n_keys), 8):
            feed(part)
        with snapshot_scope(1, incremental=True):
            storage.store(1, {"w": op.snapshot_state()})
        op.notify_checkpoint_complete(1)

        inc_bytes, cut_ms = [], []
        for cid in range(2, 2 + rounds):
            feed(rng.choice(n_keys, churn, replace=False).astype(np.int64))
            t0 = time.perf_counter()
            with snapshot_scope(cid, incremental=True):
                snap = op.snapshot_state()
            cut_ms.append((time.perf_counter() - t0) * 1000.0)
            if delta.tree_has_increment({"w": snap}):
                inc_bytes.append(delta.state_size(snap))
            storage.store(cid, {"w": snap})
            op.notify_checkpoint_complete(cid)

        full = op.snapshot_state()
        full_bytes = delta.state_size(full)
        last = storage.checkpoint_ids()[-1]
        t0 = time.perf_counter()
        restored = storage.load_latest()          # base + ordered replay
        op_r = WindowAggOperator(TumblingEventTimeWindows.of(1000),
                                 SumAggregator(jnp.float32),
                                 key_column="k", value_column="v")
        op_r.open(RuntimeContext())
        op_r.restore_state(restored["w"])
        recovery_ms = (time.perf_counter() - t0) * 1000.0
        digest_match = _tree_eq(restored["w"], full) and _tree_eq(
            op_r.snapshot_state(), full)
        ratio = (max(inc_bytes) / full_bytes) if inc_bytes else None
        return {
            "metric": "incremental checkpoint bytes + recovery at "
                      f"{churn_frac:.0%} churn",
            "ok": bool(digest_match and inc_bytes),
            "n_keys": n_keys,
            "churn_keys": churn,
            "incremental_checkpoints": len(inc_bytes),
            "full_snapshot_bytes": int(full_bytes),
            "increment_bytes_max": int(max(inc_bytes)) if inc_bytes else None,
            "increment_bytes_mean": (round(sum(inc_bytes) / len(inc_bytes))
                                     if inc_bytes else None),
            "bytes_ratio": round(ratio, 4) if ratio is not None else None,
            "increments_per_base": storage.chain_length(last) - 1,
            "compactions": storage.compactions,
            "cut_ms_max": round(max(cut_ms), 2) if cut_ms else None,
            "recovery_ms": round(recovery_ms, 1),
            "digest_match": digest_match,
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def check_incremental_budget(result: dict, budget: dict,
                             smoke: bool = False) -> list:
    """BENCH_BUDGET.json ``checkpoint_incremental`` gate.  Digest equality
    (chain restore == full snapshot) and the existence of incremental cuts
    gate UNCONDITIONALLY — a delta format that silently re-bases every cut
    or resolves to different state must never exit 0 because no byte
    ceiling was configured."""
    viol = []
    if not result.get("digest_match"):
        viol.append("incremental: chain-restored state is not "
                    "digest-identical to the full snapshot")
    floor = budget.get("min_incremental_checkpoints", 1)
    if result.get("incremental_checkpoints", 0) < floor:
        viol.append(f"incremental: {result.get('incremental_checkpoints')} "
                    f"delta cuts < floor {floor} — every cut re-based")
    cap = budget.get("max_bytes_ratio")
    ratio = result.get("bytes_ratio")
    if cap is not None and ratio is not None and ratio > cap:
        viol.append(f"incremental: delta bytes {ratio:.1%} of full "
                    f"snapshot > ceiling {cap:.0%} at "
                    f"{result.get('churn_keys')} churned keys")
    cap = budget.get("max_recovery_ms")
    rec = result.get("recovery_ms")
    if not smoke and cap is not None and rec is not None and rec > cap:
        viol.append(f"incremental: recovery {rec}ms > ceiling {cap}ms")
    return viol


# ONE diurnal implementation for --autoscale AND the scenario suite
# (ISSUE-15: twin generators drift) — promoted to testing/workload.py
from flink_tpu.testing.workload import DiurnalSource as _DiurnalSource  # noqa: E402


def run_autoscale_bench(args) -> dict:
    """``--autoscale``: the reactive autoscaler (ISSUE-14) under a diurnal
    load curve.  A stable-split :class:`_DiurnalSource` paces arrivals
    through a day curve while a seeded ``DelayBy`` on ``channel.recv``
    models a fixed per-dequeue consumer cost (so drain capacity scales
    with parallelism — the reason scale-out helps); the
    ``ReactiveAutoscaler`` watches the job's own backpressure gauges and
    rescales 2→4 at the peak and back down after it, each rescale an
    unaligned checkpoint with channel-state redistribution — no drain.
    Reports rescale count/latency, throughput recovery time after the
    scale-out, and records lost/duplicated (both MUST be 0), gated by
    BENCH_BUDGET.json ``rescale_cpu``."""
    import threading

    from flink_tpu.cluster.adaptive import (AutoscalerPolicy,
                                            ReactiveAutoscaler)
    from flink_tpu.datastream.api import StreamExecutionEnvironment
    from flink_tpu.runtime.checkpoint.storage import InMemoryCheckpointStorage
    from flink_tpu.testing import chaos
    from flink_tpu.windowing.assigners import TumblingEventTimeWindows

    smoke = args.smoke
    n_records = args.records or (150_000 if smoke else 600_000)
    n_keys = min(args.keys, 1009 if smoke else 100_003)
    batch_size = 128
    span_ms = 20_000
    from flink_tpu.connectors.sinks import CollectSink
    sink = CollectSink()
    source = _DiurnalSource(n_records, n_keys, batch_size, span_ms,
                            peak_s=0.006, trough_s=0.025)

    def plan_factory(parallelism):
        env = StreamExecutionEnvironment()
        env.set_parallelism(parallelism)
        (env.from_source(source)
         .assign_timestamps_and_watermarks(0, timestamp_column="t")
         .key_by("k")
         .window(TumblingEventTimeWindows.of(1000))
         .sum("v").add_sink(sink))
        return env.get_stream_graph("autoscale-bench").to_plan()

    scale_out_depth, scale_in_depth = 12, 2
    policy = AutoscalerPolicy(min_parallelism=2, max_parallelism=4,
                              scale_out_queue_depth=scale_out_depth,
                              scale_in_queue_depth=scale_in_depth,
                              sustain_polls=3, cooldown_ms=1500.0)
    storage = InMemoryCheckpointStorage(retain=10)
    scaler = ReactiveAutoscaler(
        plan_factory, checkpoint_storage=storage, policy=policy,
        initial_parallelism=2, poll_interval_ms=25.0,
        checkpoint_interval_ms=50, alignment_timeout_ms=100.0,
        restart_attempts=4, job_timeout_s=600.0)
    inj = chaos.FaultInjector(seed=37)
    # the consumer-cost model: every dequeue pays a fixed cost, so drain
    # capacity is proportional to the number of consuming subtasks
    inj.inject("channel.recv", chaos.DelayBy(0.010))
    timeline = []
    stop = threading.Event()

    def watch():
        t_w0 = time.monotonic()
        while not stop.is_set():
            st = scaler.status()
            timeline.append((time.monotonic() - t_w0,
                             st["signals"].get("max_queue_depth", 0),
                             len(st["parallelism_path"]),
                             st["last_rescale_duration_ms"]))
            time.sleep(0.05)

    t0 = time.monotonic()
    watcher = threading.Thread(target=watch, daemon=True)
    watcher.start()
    with chaos.installed(inj):
        scaler.start()
        scaler.join(timeout_s=600)
    stop.set()
    wall_ms = (time.monotonic() - t0) * 1000.0
    st = scaler.status()

    # exactly-once accounting: per-key window sums vs the generated data
    expected = {k: s for k, (_c, s) in source.expected_per_key().items()}
    got: dict = {}
    for r in sink.rows():
        got[int(r["k"])] = got.get(int(r["k"]), 0.0) + float(r["v"])
    lost = dup = 0.0
    for k in set(expected) | set(got):
        d = expected.get(k, 0.0) - got.get(k, 0.0)
        if d > 0:
            lost += d
        else:
            dup -= d

    # throughput recovery: time from the first rescale COMPLETING (first
    # output of the new deployment — last_rescale_duration_ms appears)
    # until queue depth is back under the scale-in threshold: the new
    # parallelism has drained the peak's backlog
    recovery_ms = None
    t_out = None
    for t, depth, path_len, dur in timeline:
        if t_out is None:
            if path_len >= 2 and dur is not None:
                t_out = t
            continue
        if depth <= scale_in_depth:
            recovery_ms = round((t - t_out) * 1000.0, 1)
            break
    if t_out is not None and recovery_ms is None:
        recovery_ms = round((timeline[-1][0] - t_out) * 1000.0, 1)

    finished = scaler.state == "Finished"
    ok = (finished and lost == 0 and dup == 0 and st["rescales"] >= 1)
    return {
        "metric": "reactive autoscaler under a diurnal load curve",
        "ok": bool(ok),
        "state": scaler.state,
        "error": scaler.error,
        "records": n_records,
        "keys": n_keys,
        "rescales": st["rescales"],
        "rollbacks": st["rollbacks"],
        "retriggers": st["retriggers"],
        "parallelism_path": st["parallelism_path"],
        "rescale_latency_ms": st["last_rescale_duration_ms"],
        "recovery_ms": recovery_ms,
        "records_lost": int(lost),
        "records_duplicated": int(dup),
        "records_per_sec": round(n_records / max(wall_ms / 1000.0, 1e-9)),
        "wall_ms": round(wall_ms, 1),
    }


def check_rescale_budget(result: dict, budget: dict,
                         smoke: bool = False) -> list:
    """BENCH_BUDGET.json ``rescale_cpu`` gate for ``--autoscale``.
    Exactly-once (zero lost, zero duplicated records) and job completion
    gate UNCONDITIONALLY — a rescale that loses records must never exit 0
    because no perf ceiling was configured."""
    viol = []
    if result.get("state") != "Finished":
        viol.append(f"autoscaled job did not finish: "
                    f"{result.get('state')} ({result.get('error')})")
    lost = result.get("records_lost")
    if lost != 0:
        viol.append(f"records_lost {lost} != 0 — rescale dropped records")
    dup = result.get("records_duplicated")
    if dup != 0:
        viol.append(f"records_duplicated {dup} != 0 — rescale replayed "
                    f"records twice")
    floor = budget.get("min_rescales", 1)
    if result.get("rescales", 0) < floor:
        viol.append(f"rescales {result.get('rescales')} < floor {floor} — "
                    f"the autoscaler never reacted to the load curve")
    cap = budget.get("max_rollbacks")
    if cap is not None and result.get("rollbacks", 0) > cap:
        viol.append(f"rollbacks {result.get('rollbacks')} > ceiling {cap}")
    cap = budget.get("max_rescale_latency_ms")
    lat = result.get("rescale_latency_ms")
    if cap is not None and lat is not None and lat > cap:
        viol.append(f"rescale latency {lat}ms > ceiling {cap}ms")
    cap = budget.get("max_recovery_ms")
    rec = result.get("recovery_ms")
    if not smoke and cap is not None and rec is not None and rec > cap:
        viol.append(f"throughput recovery {rec}ms > ceiling {cap}ms")
    return viol


def run_scenario_bench(args) -> dict:
    """``--scenario <name>|all``: the scenario suite (ISSUE-15) — named
    end-to-end exactly-once applications under the shared diurnal load
    curve.  Each scenario runs its FAULTED leg (reactive autoscaler,
    consumer-cost backpressure, nemeses armed at the peak: worker kill,
    SlowConsumer, KillDuringRescale, and — full runs — WedgedDevice;
    routed binary queryable readers at a paced QPS) plus an unfaulted
    CONTROL leg over a bit-identical stream, then verifies the committed
    transactional output is exactly-once: zero lost, zero duplicated,
    digest-identical to the control, scenario cross-checks clean.  With
    ``--check`` each scenario gates against its own BENCH_BUDGET.json
    section (``scenario_fraud_cpu`` / ``scenario_session_cpu`` /
    ``scenario_feature_cpu``)."""
    from flink_tpu.scenarios import SCENARIOS, ScenarioHarness, get_scenario

    names = (list(SCENARIOS) if args.scenario == "all"
             else [args.scenario])
    results = []
    for name in names:
        harness = ScenarioHarness(
            get_scenario(name), smoke=args.smoke,
            records=args.records or None,
            full_nemeses=not args.smoke)
        results.append(harness.run())
    return {
        "metric": "scenario suite: exactly-once applications under a "
                  "diurnal load curve",
        "ok": all(r["ok"] for r in results),
        "scenarios": results,
    }


def check_scenario_budget(result: dict, budget: dict,
                          smoke: bool = False) -> list:
    """BENCH_BUDGET.json gate for ONE scenario result.  Exactly-once
    gates UNCONDITIONALLY (even smoke, even with an empty budget
    section): records lost or duplicated, a committed digest differing
    from the unfaulted control, a failed cross-check, or an empty
    committed output must never exit 0 because no perf floor was
    configured."""
    name = result.get("scenario", "?")
    viol = []
    if result.get("state") != "Finished":
        viol.append(f"{name}: faulted job did not finish: "
                    f"{result.get('state')} ({result.get('error')})")
    if result.get("control_state") != "Finished":
        viol.append(f"{name}: control job did not finish: "
                    f"{result.get('control_state')} "
                    f"({result.get('control_error')})")
    lost = result.get("records_lost")
    if lost != 0:
        viol.append(f"{name}: records_lost {lost} != 0 — committed output "
                    f"dropped rows under chaos")
    dup = result.get("records_duplicated")
    if dup != 0:
        viol.append(f"{name}: records_duplicated {dup} != 0 — committed "
                    f"output replayed rows twice")
    if not result.get("digest_match"):
        viol.append(f"{name}: committed-sink digest differs from the "
                    f"unfaulted control")
    for v in result.get("cross_check_violations", []):
        viol.append(f"{name}: {v}")
    if sum(result.get("committed_rows", {}).values()) <= 0:
        viol.append(f"{name}: no committed output rows")
    floor = budget.get("min_rescales", 1)
    if result.get("rescales", 0) < floor:
        viol.append(f"{name}: rescales {result.get('rescales')} < floor "
                    f"{floor} — the autoscaler never reacted to the "
                    f"diurnal curve")
    cap = budget.get("max_rollbacks")
    if cap is not None and result.get("rollbacks", 0) > cap:
        viol.append(f"{name}: rollbacks {result.get('rollbacks')} > "
                    f"ceiling {cap}")
    if not smoke:
        floor = budget.get("min_peak_rps")
        peak = result.get("peak_records_per_sec")
        if floor is not None and (peak or 0.0) < floor:
            viol.append(f"{name}: sustained peak {peak} rec/s < floor "
                        f"{floor}")
        cap = budget.get("max_p99_ms")
        p99 = result.get("latency_p99_ms")
        if cap is not None and p99 is not None and p99 > cap:
            viol.append(f"{name}: end-to-end p99 {p99}ms > ceiling "
                        f"{cap}ms")
        floor = budget.get("min_lookups_per_sec")
        q = result.get("queryable") or {}
        if floor is not None and q:
            lps = q.get("lookups_per_sec", 0.0)
            if lps < floor:
                viol.append(f"{name}: queryable reads {lps}/s < floor "
                            f"{floor}/s")
    return viol


def run_ha_kill_bench(args) -> dict:
    """``--ha-kill``: coordinator high availability under fire (ISSUE-20).
    Leader A runs a scenario under a FileHaStore lease; a
    ``KillCoordinator`` nemesis fails A's lease renewal at the diurnal
    peak (loud demotion — A keeps executing as a ZOMBIE); standby B
    acquires the lease at epoch + 1, proves the zombie's stale-epoch
    checkpoint completions are fenced by the HA store, recovers the job
    from the completed-checkpoint pointer (increment chains included) and
    finishes it.  Committed output must be exactly-once and
    digest-identical to an unfaulted control; with ``--check`` gates
    against BENCH_BUDGET.json ``ha_cpu``."""
    from flink_tpu.scenarios import ScenarioHarness, get_scenario

    name = args.scenario or "fraud_detection"
    harness = ScenarioHarness(get_scenario(name), smoke=args.smoke,
                              records=args.records or None)
    result = harness.run_ha_kill()
    return {
        "metric": "coordinator HA: leader kill at the peak, epoch-fenced "
                  "takeover from the HA store",
        "ok": bool(result.get("ok")),
        "ha_kill": result,
    }


def check_ha_budget(result: dict, budget: dict, smoke: bool = False) -> list:
    """BENCH_BUDGET.json ``ha_cpu`` gate for one ``--ha-kill`` result.
    Exactly-once and the fencing probes gate UNCONDITIONALLY (even smoke,
    even with an empty budget section): a zombie ex-leader completing a
    checkpoint or committing a 2PC transaction, lost/duplicated rows, or
    a digest mismatch must never exit 0 because no ceiling was
    configured.  The recovery-time ceiling is full-run only (smoke hosts
    jitter too much for a wall-clock gate)."""
    name = result.get("scenario", "?")
    viol = []
    if result.get("state") != "FINISHED":
        viol.append(f"{name}: recovered job did not finish: "
                    f"{result.get('state')}")
    if result.get("control_state") != "Finished":
        viol.append(f"{name}: control job did not finish: "
                    f"{result.get('control_state')} "
                    f"({result.get('control_error')})")
    epochs = result.get("leader_epochs") or []
    if len(epochs) != 2 or epochs[1] <= epochs[0]:
        viol.append(f"{name}: takeover did not advance the leader epoch "
                    f"({epochs})")
    if not result.get("stale_pointer_rejected"):
        viol.append(f"{name}: zombie ex-leader's checkpoint completion "
                    f"was NOT fenced by the HA store")
    if not result.get("stale_commit_fenced"):
        viol.append(f"{name}: a 2PC commit under the stale epoch was NOT "
                    f"fenced")
    lost = result.get("records_lost")
    if lost != 0:
        viol.append(f"{name}: records_lost {lost} != 0 across the "
                    f"coordinator kill")
    dup = result.get("records_duplicated")
    if dup != 0:
        viol.append(f"{name}: records_duplicated {dup} != 0 across the "
                    f"coordinator kill")
    if not result.get("digest_match"):
        viol.append(f"{name}: committed-sink digest differs from the "
                    f"unfaulted control")
    if sum(result.get("committed_rows", {}).values()) <= 0:
        viol.append(f"{name}: no committed output rows")
    if not smoke:
        cap = budget.get("max_recovery_ms")
        rec = result.get("recovery_ms")
        if cap is not None and rec is not None and rec > cap:
            viol.append(f"{name}: recovery {rec}ms > ceiling {cap}ms "
                        f"(demotion -> new-epoch checkpoint completed)")
    return viol


def _cep_pattern(window_ms: int):
    """Fraud-detection shape (examples/fraud_detection.py as a PATTERN):
    a small 'bait' transaction followed by a large 'strike' on the same
    key within 4 windows."""
    from flink_tpu.cep import Pattern

    return (Pattern.begin("small")
            .where(lambda c: np.asarray(c["v"]) < 30.0)
            .followed_by("large")
            .where(lambda c: np.asarray(c["v"]) > 570.0)
            .within(4 * window_ms))


def _cep_batches(n_records: int, n_keys: int, batch_size: int,
                 window_ms: int, seed: int = 23):
    rng = np.random.default_rng(seed)
    batches = []
    t = 0
    for lo in range(0, n_records, batch_size):
        b = min(batch_size, n_records - lo)
        keys = rng.integers(0, n_keys, b).astype(np.int64)
        vals = (rng.random(b) * 600.0).astype(np.float64)
        ts = t + np.sort(rng.integers(0, window_ms, b)).astype(np.int64)
        t += window_ms
        batches.append((keys, vals, ts))
    return batches


def run_cep_bench(args) -> dict:
    """``--cep``: the vectorized CEP engine (ISSUE-8 tentpole) on a
    fraud-detection-style pattern over the 1M-key stream.  Reports
    events/sec + matches/sec + the partial-match high-water mark for the
    batched kernel, the interpreted NFA's rate on the same stream (time-
    budgeted — it is the per-event Python loop being replaced), the
    engine ``auto`` calibration picked on this backend, and a small-prefix
    equivalence check (identical matches, identical order).  With
    ``--check`` the result gates against BENCH_BUDGET.json ``cep_cpu``."""
    from flink_tpu.cep import CepOperator
    from flink_tpu.core.batch import RecordBatch, Watermark

    n_records = args.records or (1 << 17 if args.smoke else 1 << 22)
    n_keys = min(args.keys, n_records)
    window_ms = args.window_ms
    batches = _cep_batches(n_records, n_keys, args.batch_size, window_ms)
    pattern = _cep_pattern(window_ms)
    select = (lambda m: {"k": m["small"][0]["k"],
                         "amount": m["large"][0]["v"]})

    def one_pass(mode, budget_s=None):
        op = CepOperator(pattern, "k", select, vectorized=mode)
        t0 = time.perf_counter()
        n = matches = 0
        for keys, vals, ts in batches:
            out = op.process_batch(
                RecordBatch({"k": keys, "v": vals}, timestamps=ts))
            out += op.process_watermark(Watermark(int(ts.max()) - 1))
            matches += sum(len(b) for b in out if hasattr(b, "columns"))
            n += keys.size
            if budget_s and time.perf_counter() - t0 > budget_s:
                break
        if not budget_s:
            tail = op.end_input()
            matches += sum(len(b) for b in tail if hasattr(b, "columns"))
        elapsed = time.perf_counter() - t0
        return n / elapsed, matches / elapsed, matches, op.cep_stats()

    # small-prefix equivalence: both engines, identical matches in order
    def mini_rows(mode):
        op = CepOperator(pattern, "k", select, vectorized=mode)
        rows = []
        for keys, vals, ts in _cep_batches(1 << 14, 4096, 4096, window_ms):
            out = op.process_batch(
                RecordBatch({"k": keys, "v": vals}, timestamps=ts))
            out += op.process_watermark(Watermark(int(ts.max()) - 1))
            for b in out:
                for i in range(len(b)):
                    rows.append((int(np.asarray(b.column("k"))[i]),
                                 float(np.asarray(b.column("amount"))[i]),
                                 int(np.asarray(b.timestamps)[i])))
        return rows

    equivalence_ok = mini_rows("on") == mini_rows("off")

    vec = _best_of(lambda: one_pass("on"), 2 if args.smoke else 3)
    interp = one_pass("off", budget_s=5.0 if args.smoke else 30.0)
    auto_op = CepOperator(pattern, "k", select, vectorized="auto")
    k0, v0, t0 = batches[0]
    auto_op.process_batch(RecordBatch({"k": k0[:1024], "v": v0[:1024]},
                                      timestamps=t0[:1024]))
    auto_engine = auto_op.cep_stats()["engine"]

    eps, mps, matches, stats = vec
    i_eps, i_mps, _im, _is = interp
    detail = {
        "events_per_sec": round(eps, 1),
        "matches": matches,
        "partials_high_water": stats["partials_high_water"],
        "interpreted_events_per_sec": round(i_eps, 1),
        "interpreted_matches_per_sec": round(i_mps, 1),
        "speedup_vs_interpreted": round(mps / i_mps, 2) if i_mps else None,
        "auto_engine": auto_engine,
        "equivalence_ok": equivalence_ok,
        "n_records": n_records,
        "n_keys": n_keys,
        "vectorized_drains": stats["vectorized_drains"],
        "degraded": stats["degraded"],
    }
    return {
        "metric": f"matches/sec (CEP fraud pattern, {n_keys} keys, "
                  f"vectorized NFA kernel)",
        "value": round(mps, 1),
        "unit": "matches/sec",
        "ok": equivalence_ok and stats["degraded"] == 0,
        "details": detail,
    }


def check_cep_budget(result: dict, budget: dict, smoke: bool = False) -> list:
    """``--cep`` result vs the BENCH_BUDGET ``cep_cpu`` section: a
    matches/sec floor (full runs), a speedup-vs-interpreted floor (the
    acceptance bar — the batched kernel must beat the per-event Python
    loop; relaxed at smoke size where fixed costs dominate), and the
    equivalence check (never exit 0 on divergent matches)."""
    viol = []
    d = result["details"]
    if not d.get("equivalence_ok"):
        viol.append("vectorized-vs-interpreted equivalence check failed")
    floor = budget.get("min_matches_per_sec")
    if floor is not None and not smoke and result["value"] < floor:
        viol.append(f"matches/sec {result['value']:.0f} < floor {floor:.0f}")
    sp = d.get("speedup_vs_interpreted")
    sp_floor = budget.get("min_speedup_smoke" if smoke
                          else "min_speedup_vs_interpreted")
    if sp_floor is not None and sp is None:
        # the interpreted leg produced no matches: the A/B measured
        # nothing, which must not read as "bar met"
        viol.append("speedup vs interpreted unmeasured (interpreted pass "
                    "recorded zero matches) — the acceptance bar cannot "
                    "be skipped")
    elif sp is not None and sp_floor is not None and sp < sp_floor:
        viol.append(f"speedup vs interpreted {sp} < floor {sp_floor} "
                    f"(the batched kernel is not paying for itself)")
    if d.get("auto_engine") not in ("vectorized", "interpreted"):
        viol.append(f"auto calibration resolved no engine: "
                    f"{d.get('auto_engine')!r}")
    return viol


def run_queryable_bench(args) -> dict:
    """``--queryable``: the serving tier at production QPS (ISSUE-13)
    against a RUNNING 1M-key window job.  One pass drains the stream with
    no read load (baseline records/sec), a second pass drains the SAME
    stream while ``--qps-clients`` pooled clients sustain
    ``--qps-target`` aggregate lookups/sec through the BINARY COLUMNAR
    wire protocol with client-side key-group routing — alternating
    ``live`` and ``checkpoint`` consistency.  Reports lookups/sec,
    client-side p50/p99 AND the server-side service-time percentiles
    (lookup + serialization measured in the handler — the honest number
    on a GIL-loaded box), protocol + routing mode, cache hit rate, the
    replicas' worst observed lag, the job's throughput under load as a
    FRACTION of unloaded (the <10% tax acceptance), a live-equality
    check (wire values == the view's fire-time values) and a
    binary==JSON answer-equality check.  With ``--check`` gates against
    BENCH_BUDGET.json ``queryable_cpu``."""
    from flink_tpu.core.batch import RecordBatch, Watermark
    from flink_tpu.queryable import (QueryableStateClientPool,
                                     QueryableStateService,
                                     QueryableStateSpec)
    from flink_tpu.queryable import wire as qwire

    n_records = args.records or (1 << 17 if args.smoke else 1 << 22)
    n_keys = min(args.keys, n_records)
    window_ms = args.window_ms
    # smoke shrinks the batch size too: the checkpoint feed must run at
    # least a few times per pass or the replica/staleness leg measures
    # nothing
    batch_size = min(args.batch_size, 1 << 14) if args.smoke \
        else args.batch_size
    batches = make_batches(n_records, n_keys, batch_size, window_ms)
    ckpt_every = max(1, min(args.checkpoint_every, len(batches) // 4))
    # client count trades per-request RTT for in-flight concurrency: the
    # drain's jitted megastep holds the GIL in multi-ms stretches, so a
    # single request's round trip can span several dispatch windows —
    # sustained qps = in-flight / RTT, and the fleet is paced to the same
    # aggregate target regardless of its size
    n_clients = args.qps_clients or (2 if args.smoke else 16)
    batch_keys = args.qps_batch_keys
    qps_target = args.qps_target
    # sustained-rate pacing: each client fires every `interval` seconds so
    # the fleet lands on the aggregate target — the acceptance is "the
    # target RATE sustained with <10% hot-path tax", not "max rate at any
    # tax" (an unthrottled fleet measures GIL contention, not serving)
    interval = (n_clients * batch_keys / qps_target) if qps_target else 0.0

    # the serving window must be long enough to SUSTAIN the target rate
    # (the one-dispatch job drains 4M records in well under a second):
    # repeat the stream with advancing timestamps — same keys (warm steady
    # state), fresh windows every repeat, live fires throughout
    repeats = 1 if args.smoke else 8
    max_ts = max(int(ts.max()) for _k, _v, ts in batches)
    ts_span = ((max_ts // window_ms) + 2) * window_ms
    # checkpoint cadence spans the WHOLE run (~4 checkpoints however many
    # repeats): each 1M-key ingest is real background work on the feed
    # thread, and production checkpoints are time-based, not
    # per-2M-records
    ckpt_every = max(ckpt_every, (len(batches) * repeats) // 4 or 1)

    def drain(op, svc=None, n_repeats=1):
        """The job under test: the standard drain loop over ``n_repeats``
        timestamp-shifted passes of the stream (same keys — warm steady
        state; fresh windows every repeat), snapshotting into the serving
        tier's checkpoint feed (the MiniCluster _complete_checkpoint
        path, inlined)."""
        cid = 0
        step = 0
        t0 = time.perf_counter()
        for r in range(n_repeats):
            off = r * ts_span
            for k, v, ts in batches:
                tso = ts + off if off else ts
                op.process_batch(RecordBatch({"k": k, "v": v},
                                             timestamps=tso))
                op.process_watermark(Watermark(int(tso.max()) - 1))
                step += 1
                if svc is not None and step % ckpt_every == 0:
                    cid += 1
                    op.prepare_snapshot_pre_barrier()
                    snap = op.snapshot_state()
                    svc.on_checkpoint_complete(
                        cid, {"win": {"subtasks": [{"operator": snap}]}})
                    op.notify_checkpoint_complete(cid)
        op.flush_pipeline()
        elapsed = time.perf_counter() - t0
        op.end_input()
        return n_records * n_repeats / elapsed, cid

    # warm-up: one throwaway prefix drain + snapshot so pass 1 measures
    # the job, not XLA compiles / process-wide sync
    # calibration / allocator warm-up (pass ordering must not bias the
    # under-load-vs-unloaded fraction)
    warm = _build_op(window_ms, "host", args.device_sync,
                     pipeline_depth=args.pipeline_depth,
                     native_shards=args.native_shards)
    for k, v, ts in batches[: max(1, len(batches) // 8)]:
        warm.process_batch(RecordBatch({"k": k, "v": v}, timestamps=ts))
        warm.process_watermark(Watermark(int(ts.max()) - 1))
    warm.flush_pipeline()
    warm.prepare_snapshot_pre_barrier()
    warm.snapshot_state()
    warm.end_input()
    del warm

    # a serving process trades a sliver of drain throughput for request
    # latency: the default 5ms GIL switch interval parks a handler thread
    # for milliseconds per slice behind the drain loop.  Applied to BOTH
    # passes so the fraction stays apples-to-apples.
    switch0 = sys.getswitchinterval()
    sys.setswitchinterval(0.001)

    # interleaved rounds of (unloaded leg, loaded leg), best of each —
    # symmetric, so this class of vCPU host's 10%+ run-to-run steal noise
    # hits both sides of the under-load fraction equally.  EVERY leg runs
    # the IDENTICAL job — queryable views published, checkpoints
    # snapshotted and replica-ingested — so the fraction isolates the
    # READ load, not the checkpoint stream the job runs either way.
    rounds = 1 if args.smoke else 2

    def _leg_op():
        return _build_op(window_ms, "host", args.device_sync,
                         pipeline_depth=args.pipeline_depth,
                         native_shards=args.native_shards, queryable="agg")

    # ONE serving tier + server for the whole bench: loaded legs
    # re-register their op's live view (register_views replaces), the
    # replica keeps ingesting whichever loaded leg is running
    import jax.numpy as jnp

    from flink_tpu.core.functions import SumAggregator
    svc = QueryableStateService()
    svc.add_replica("agg", QueryableStateSpec("agg", "win", "k",
                                              SumAggregator(jnp.float32)))
    server = svc.start_server()

    # the client fleet runs OUT-OF-PROCESS, like production readers: a
    # client thread inside the job process measures GIL scheduling, not
    # serving.  Only the server (its handler threads) shares the job's
    # process — that contention IS the hot-path tax under test.  Clients
    # pause between loaded legs (stdio go/pause protocol).
    import subprocess as _sp
    bench_path = os.path.abspath(__file__)
    cprocs = []
    for c in range(n_clients):
        cenv = dict(os.environ)
        # pin CPU in the client processes: they never run jax work, and
        # a chip belongs to one process at a time — the job process
        # holds it, so a client that reached for it would be refused
        cenv["JAX_PLATFORMS"] = "cpu"
        cprocs.append(_sp.Popen(
            [sys.executable, bench_path, "--_qps-client",
             "--_qps-host", str(server.host),
             "--_qps-port", str(server.port),
             "--_qps-seed", str(100 + c),
             "--_qps-interval-us", str(interval * 1e6),
             "--qps-batch-keys", str(batch_keys),
             "--keys", str(n_keys)],
            stdin=_sp.PIPE, stdout=_sp.PIPE, text=True, env=cenv))
    counts = {"lookups": 0, "errors": 0, "max_lag": 0, "routed_batches": 0}
    lat_ms: list = []
    ready = 0
    for p in cprocs:
        line = p.stdout.readline()
        if line.strip() == "READY":
            ready += 1
    if ready < n_clients:
        counts["errors"] += n_clients - ready

    def _fleet(cmd: str) -> None:
        for p in cprocs:
            try:
                p.stdin.write(cmd + "\n")
                p.stdin.flush()
            except OSError:
                pass

    rps_no_load = 0.0
    rps_load = 0.0
    q_elapsed = 0.0
    n_ckpts = 0
    op = None
    for _round in range(rounds):
        # unloaded leg
        op0 = _leg_op()
        svc0 = QueryableStateService()
        svc0.register_views("agg", [op0.queryable_view()], 1, 128)
        svc0.add_replica("agg", QueryableStateSpec("agg", "win", "k",
                                                   op0.agg))
        rps, _ = drain(op0, svc0, n_repeats=repeats)
        svc0.drain_feed()
        svc0.close()
        rps_no_load = max(rps_no_load, rps)
        del op0
        # loaded leg: same job + the paced client fleet
        op = _leg_op()
        svc.register_views("agg", [op.queryable_view()], 1, 128)
        q_t0 = time.perf_counter()
        _fleet("go")
        rps, cids = drain(op, svc, n_repeats=repeats)
        _fleet("pause")
        q_elapsed += time.perf_counter() - q_t0
        rps_load = max(rps_load, rps)
        n_ckpts += cids
        if _round < rounds - 1:
            op.end_input()
    _fleet("stop")
    for p in cprocs:
        try:
            out, _ = p.communicate(timeout=60)
        except _sp.TimeoutExpired:
            p.kill()
            counts["errors"] += 1
            continue
        stats_line = next((ln for ln in out.splitlines()
                           if ln.startswith("STATS ")), None)
        if stats_line is None:
            counts["errors"] += 1
            continue
        st = json.loads(stats_line[len("STATS "):])
        lat_ms.extend(st["lat_ms"])
        counts["lookups"] += st["lookups"]
        counts["errors"] += st["errors"]
        counts["max_lag"] = max(counts["max_lag"], st["max_lag"])
        counts["routed_batches"] += st["routed_batches"]

    # live equality over the wire: served values must equal the view's
    # fire-time values EXACTLY (the server adds serialization, not math);
    # and the binary answer must be bit-identical to the JSON answer for
    # the same keys (two encodings, one contract)
    view = op.queryable_view()
    jpool = QueryableStateClientPool(server.host, server.port)  # pure JSON
    bpool = QueryableStateClientPool(server.host, server.port,
                                     protocol="binary", routing=True)
    rngq = np.random.default_rng(5)
    sample = rngq.integers(0, n_keys, 256).astype(int).tolist()
    json_ans = jpool.get_batch("agg", sample, consistency="live")
    vf, vv, _vt = view.lookup_batch(np.asarray(sample, np.int64))
    live_equal = (json_ans["found"] == vf.tolist()
                  and all((w is None and d is None) or w == d
                          for w, d in zip(json_ans["values"], vv)))
    bin_json_equal = True
    for cons in ("live", "checkpoint"):
        j = jpool.get_batch("agg", sample, consistency=cons)
        bf, bc, _bt = bpool.get_batch_columnar(
            "agg", np.asarray(sample, np.int64), consistency=cons)
        bvals = qwire.values_from_columnar(bf, bc)
        if j["found"] != bf.tolist() or any(
                not ((w is None and d is None) or w == d)
                for w, d in zip(j["values"], bvals)):
            bin_json_equal = False
    jpool.close()
    bpool.close()
    svc.drain_feed()
    final = svc.stats()
    svc.close()
    sys.setswitchinterval(switch0)

    lat = np.asarray(lat_ms) if lat_ms else np.zeros(1)
    qps = counts["lookups"] / max(q_elapsed, 1e-9)
    detail = {
        "n_records": n_records,
        "n_keys": n_keys,
        "clients": n_clients,
        "keys_per_request": batch_keys,
        "protocol": "binary",
        "routing": "client" if counts["routed_batches"] else "server",
        "qps_target": qps_target,
        "lookups": counts["lookups"],
        "lookup_errors": counts["errors"],
        "lookups_per_sec": round(qps, 1),
        "lookup_p50_ms": round(float(np.percentile(lat, 50)), 2),
        "lookup_p99_ms": round(float(np.percentile(lat, 99)), 2),
        # server-side service time (lookup + serialization in the
        # handler): the client-side p99 above also measures GIL stalls of
        # this 2-vCPU box; this one measures the server
        "serve_p50_ms": final.get("serve_p50_ms"),
        "serve_p99_ms": final.get("serve_p99_ms"),
        "cache_hit_rate": final.get("cache_hit_rate", 0.0),
        "records_per_sec_no_load": round(rps_no_load, 1),
        "records_per_sec_under_load": round(rps_load, 1),
        "rps_under_load_frac": round(rps_load / max(rps_no_load, 1e-9), 3),
        "checkpoints_fed": n_ckpts,
        "max_replica_lag_checkpoints": max(
            counts["max_lag"], final["replica_lag_checkpoints"]),
        "live_equality_ok": live_equal,
        "binary_json_equal_ok": bin_json_equal,
        "server_lookups_total": final["lookups_total"],
    }
    return {
        "metric": f"batched lookups/sec ({n_clients} clients x "
                  f"{batch_keys}-key binary columnar requests, "
                  f"client-routed, against the running "
                  f"{n_keys}-key window job, live+checkpoint)",
        "value": round(qps, 1),
        "unit": "lookups/sec",
        "ok": live_equal and bin_json_equal and counts["errors"] == 0,
        "details": detail,
    }


def _qps_client_main(args) -> int:
    """Hidden ``--_qps-client`` worker: ONE out-of-process queryable
    client of the ``--queryable`` bench.  Binary columnar protocol,
    client-side key-group routing, constant-arrival-rate pacing (the wrk2
    model: requests are DUE on a fixed schedule; after a stall the client
    catches up to a bounded backlog so the offered rate stays the
    target).  Parent protocol over stdio: prints ``READY``, then cycles
    on ``go``/``pause`` lines (the bench interleaves loaded and unloaded
    legs), stops on ``stop``/EOF and prints ``STATS <json>``."""
    import threading as _th

    from flink_tpu.queryable import QueryableStateClientPool

    state = {"cmd": "wait"}

    def _stdin_watch():
        for line in sys.stdin:
            cmd = line.strip()
            if cmd in ("go", "pause", "stop"):
                state["cmd"] = cmd
                if cmd == "stop":
                    return
        state["cmd"] = "stop"

    _th.Thread(target=_stdin_watch, daemon=True).start()
    pool = QueryableStateClientPool(args._qps_host, args._qps_port,
                                    size=2, retries=1,
                                    protocol="binary", routing=True)
    rng = np.random.default_rng(args._qps_seed)
    interval = args._qps_interval_us / 1e6
    batch_keys = args.qps_batch_keys
    n_keys = args.keys
    backlog_cap = max(4, int(1.0 / interval)) if interval else 0
    print("READY", flush=True)
    lat, lookups, errors, max_lag = [], 0, 0, 0
    i = 0
    while state["cmd"] != "stop":
        if state["cmd"] != "go":
            time.sleep(0.005)
            continue
        # entering a loaded leg: fresh schedule (pause time is not debt)
        t_start = time.perf_counter() + (rng.uniform(0, interval)
                                         if interval else 0.0)
        fired = 0
        while state["cmd"] == "go":
            if interval:
                due = (time.perf_counter() - t_start) / interval
                if fired >= due:
                    time.sleep(min((fired - due + 1) * interval, 0.02))
                    continue
                # bounded catch-up: after a stall (a 1M-key snapshot
                # stretch holds the server's GIL for ~300ms) the client
                # replays up to ONE SECOND of missed schedule, so the
                # offered rate averages the target instead of
                # target x uptime — any older backlog is dropped rather
                # than burst at the window's end
                fired = max(fired + 1, int(due) - backlog_cap)
            keys = rng.integers(0, n_keys, batch_keys)    # stays int64
            cons = "checkpoint" if i % 2 else "live"
            i += 1
            t0 = time.perf_counter()
            try:
                _f, _c, tags = pool.get_batch_columnar("agg", keys,
                                                       consistency=cons)
            except (RuntimeError, ConnectionError):
                errors += 1
                continue
            if len(lat) < 20000:
                lat.append(round((time.perf_counter() - t0) * 1e3, 4))
            lookups += batch_keys
            max_lag = max(max_lag,
                          tags.get("replica_lag_checkpoints") or 0)
    routed = pool.stats["routed_batches"]
    pool.close()
    print("STATS " + json.dumps(
        {"lookups": lookups, "errors": errors, "max_lag": max_lag,
         "routed_batches": routed, "lat_ms": lat}), flush=True)
    return 0


def check_queryable_budget(result: dict, budget: dict,
                           smoke: bool = False) -> list:
    """``--queryable`` vs BENCH_BUDGET ``queryable_cpu``: a lookups/sec
    floor and a hot-path throughput-tax floor as a FRACTION of unloaded
    (full runs — smoke sizes are dominated by fixed costs), a client-side
    p99 ceiling, a replica staleness ceiling, and the unconditional
    equality checks — live wire values == the view's fire-time values,
    and binary answers == JSON answers — which never exit 0 on a
    divergence, smoke included."""
    viol = []
    d = result["details"]
    if not d.get("live_equality_ok"):
        viol.append("live reads over the wire diverge from the view's "
                    "fire-time values")
    if "binary_json_equal_ok" in d and not d["binary_json_equal_ok"]:
        viol.append("binary columnar answers diverge from JSON answers "
                    "for the same keys (two encodings must share one "
                    "contract)")
    if d.get("lookup_errors"):
        viol.append(f"{d['lookup_errors']} lookup requests failed after "
                    f"pooled-client retries")
    floor = budget.get("min_lookups_per_sec")
    if floor is not None and not smoke and result["value"] < floor:
        viol.append(f"lookups/sec {result['value']:.0f} < floor {floor:.0f}")
    p99_cap = budget.get("max_p99_ms")
    if p99_cap is not None and d["lookup_p99_ms"] > p99_cap:
        viol.append(f"lookup p99 {d['lookup_p99_ms']}ms > ceiling "
                    f"{p99_cap}ms")
    serve_cap = budget.get("max_serve_p99_ms")
    if serve_cap is not None and d.get("serve_p99_ms") is not None \
            and d["serve_p99_ms"] > serve_cap:
        viol.append(f"server-side serve p99 {d['serve_p99_ms']}ms > "
                    f"ceiling {serve_cap}ms")
    lag_cap = budget.get("max_replica_lag_checkpoints")
    if lag_cap is not None \
            and d["max_replica_lag_checkpoints"] > lag_cap:
        viol.append(f"replica lag {d['max_replica_lag_checkpoints']} "
                    f"checkpoints > ceiling {lag_cap} (the replica feed "
                    f"is not keeping up with the checkpoint stream)")
    # hot-path non-interference, as a fraction of the unloaded run (the
    # ISSUE-13 acceptance: under-load throughput >= 0.90 of unloaded)
    frac_floor = budget.get("min_rps_under_load_frac")
    if frac_floor is not None and not smoke \
            and d["rps_under_load_frac"] < frac_floor:
        viol.append(f"records/sec under query load is "
                    f"{d['rps_under_load_frac']:.3f} of unloaded < floor "
                    f"{frac_floor} (reads are taxing the hot path)")
    # legacy absolute floor, honored when a budget still carries it
    rps_floor = budget.get("min_rps_under_load")
    if rps_floor is not None and not smoke \
            and d["records_per_sec_under_load"] < rps_floor:
        viol.append(f"records/sec under query load "
                    f"{d['records_per_sec_under_load']:.0f} < floor "
                    f"{rps_floor:.0f} (reads are stealing the hot path)")
    return viol


def run_mesh_bench(args) -> dict:
    """``--mesh-devices N``: the sharded hot path as ONE logical operator
    over an N-device mesh (forced host devices on CPU — see
    ``_early_mesh_device_flags``).  Reports records/sec/**pod** alongside
    records/sec/chip, the per-shard probe_mirror breakdown (the wall
    decomposed into N independent probes), and the restore+replay digest
    check — the multi-chip twin of the headline run."""
    import jax

    D = args.mesh_devices
    avail = len(jax.devices())
    if avail < D:
        return {"metric": "records/sec/pod (mesh sharded hot path)",
                "ok": False,
                "error": f"{D} mesh devices requested, {avail} visible "
                         f"(CPU targets force host devices automatically; "
                         f"was JAX initialized before the flag?)"}
    n_records = args.records or (1 << 18 if args.smoke else 1 << 22)
    n_keys = min(args.keys, n_records)
    batches = make_batches(n_records, n_keys, args.batch_size,
                           args.window_ms)
    (rps, fired, snaps, mid, digests, phases, bytes_, shard_ns,
     op) = run_tpu_native(
        batches, args.window_ms, args.checkpoint_every,
        emit_tier=args.emit_tier, device_sync=args.device_sync,
        timed_passes=2 if args.smoke else 3,
        pipeline_depth=args.pipeline_depth,
        native_shards=args.native_shards, mesh_devices=D,
        # size the ring to the workload so the key-group-range blocks are
        # POPULATED on every device (capacity-sized blocks would park all
        # live rows on shard 0 at small key counts)
        key_capacity=n_keys)
    replay_ok = replay_check(batches, args.window_ms, mid, digests,
                             args.emit_tier, args.device_sync,
                             pipeline_depth=args.pipeline_depth,
                             native_shards=args.native_shards,
                             mesh_devices=D, key_capacity=n_keys)
    ns = phases.pop("elapsed", 1)
    per_shard_ms = [round(v / 1e6, 1)
                    for v in shard_ns.get("probe_mirror", [])]
    detail = {
        "mesh_devices": D,
        "platform": jax.devices()[0].platform,
        "phases_ms": {k: round(v / 1e6, 1)
                      for k, v in sorted(phases.items())},
        "probe_mirror_shard_ms": per_shard_ms,
        "elapsed_ms": round(ns / 1e6, 1),
        "h2d_mb": round(bytes_.get("h2d", 0) / 1e6, 2),
        "windows_fired": fired,
        "snapshots_in_timed_run": snaps,
        "restore_replay_ok": replay_ok,
        "emit_tier": args.emit_tier,
        "device_sync": op.device_sync_mode,
        # --mesh-devices 1 is the single-chip leg of the comparison: the
        # plain operator has no shard layout, its "manifest" is one block
        "shard_manifest": ([
            {"shard": d, "rows": list(op.shard_layout().row_range(d))}
            for d in range(D)] if hasattr(op, "shard_layout")
            else [{"shard": 0, "rows": [0, op._K]}]),
    }
    return {
        "metric": f"records/sec/pod (1M-key tumbling sum, "
                  f"{detail['platform']} mesh x{D}, checkpointing every "
                  f"{args.checkpoint_every} batches)",
        "value": round(rps, 1),
        "unit": "records/sec",
        "records_per_sec_pod": round(rps, 1),
        "records_per_sec_chip": round(rps / D, 1),
        "ok": replay_ok,
        "details": detail,
    }


def check_mesh_budget(result: dict, budget: dict) -> list:
    """``--mesh-devices`` result vs the BENCH_BUDGET ``mesh_cpu`` section:
    a pod-throughput floor, per-phase ceilings, and a per-shard probe
    share ceiling — the probe_mirror wall must actually be DECOMPOSED
    (one shard hogging the whole wall means the sharding is fictional)."""
    viol = []
    if "error" in result:
        return [result["error"]]
    floor = budget.get("min_rps_pod")
    if floor is not None and result["records_per_sec_pod"] < floor:
        viol.append(f"rec/s/pod {result['records_per_sec_pod']:.0f} < "
                    f"floor {floor:.0f}")
    phases = result["details"]["phases_ms"]
    for name, cap in budget.get("max_phase_ms", {}).items():
        got = phases.get(name)
        if got is not None and got > cap:
            viol.append(f"phase {name} {got}ms > budget {cap}ms")
    share_cap = budget.get("max_shard_probe_share")
    per_shard = result["details"].get("probe_mirror_shard_ms") or []
    live = [v for v in per_shard if v > 0]
    # exempt single-live-shard runs: EXACT zeros only come from the serial
    # C pass (sub-threshold batches write shard_ns[0]=total, rest 0 by
    # contract).  A genuinely parked fold cannot masquerade: in the
    # sharded pass every shard scans all records (the ownership check is
    # per-record), so even a shard owning zero slots reports nonzero ns
    # and the share check sees it
    if share_cap is not None and len(live) > 1:
        share = max(live) / sum(live)
        if share > share_cap:
            viol.append(
                f"probe shard share {share:.0%} > ceiling {share_cap:.0%} "
                f"(per-shard ms {per_shard}: the probe_mirror wall is not "
                f"decomposed)")
    if not result.get("ok"):
        viol.append("restore/replay check failed")
    return viol


def check_budget(result: dict, budget: dict) -> list:
    """Compare one bench result against a BENCH_BUDGET.json section; returns
    human-readable violations (empty = pass).  The in-repo regression gate
    (VERDICT r3 weak #3): throughput floor, p99 ceiling, per-phase ceilings,
    plus (where budgeted) a vs-numpy floor — the framework must not lose to
    flat single-core numpy on its own fallback tier — and a probe_mirror
    share-of-elapsed ceiling guarding the pipelined host path."""
    viol = []
    if result["value"] < budget["min_rps"]:
        viol.append(f"rec/s {result['value']:.0f} < floor "
                    f"{budget['min_rps']:.0f}")
    p99 = result["p99_fire_latency_ms"]
    if p99 > budget["max_p99_ms"]:
        viol.append(f"p99 fire latency {p99}ms > ceiling "
                    f"{budget['max_p99_ms']}ms")
    phases = result["details"]["phases_ms"]
    for name, cap in budget.get("max_phase_ms", {}).items():
        got = phases.get(name)
        if got is not None and got > cap:
            viol.append(f"phase {name} {got}ms > budget {cap}ms")
    floor = budget.get("min_vs_numpy")
    vs_np = result.get("vs_numpy_baseline")
    if floor is not None and vs_np is not None and vs_np < floor:
        viol.append(f"vs_numpy_baseline {vs_np} < floor {floor}")
    frac = budget.get("max_probe_mirror_frac")
    elapsed = result["details"].get("elapsed_ms")
    pm = phases.get("probe_mirror")
    if frac is not None and pm is not None and elapsed:
        share = pm / elapsed
        if share > frac:
            viol.append(f"probe_mirror {pm}ms is {share:.0%} of elapsed "
                        f"{elapsed}ms > ceiling {frac:.0%}")
    return viol


def run_trace_bench(args, batches) -> dict:
    """The --trace legs: a tracing-OFF and a tracing-ON run of the SAME
    headline workload (same warmup/checkpoint cadence, best-of-2 each,
    back-to-back so host drift mostly cancels), plus the Chrome
    trace-event artifact from the ON leg's span journal.  Returns the
    ``details["trace"]`` dict; the artifact itself is written to
    ``args.trace``."""
    from flink_tpu.observability import tracing

    kw = dict(emit_tier=args.emit_tier, device_sync=args.device_sync,
              timed_passes=2, pipeline_depth=args.pipeline_depth,
              native_shards=args.native_shards)
    off_rps = run_tpu_native(batches, args.window_ms,
                             args.checkpoint_every, **kw)[0]
    journal = tracing.install(tracing.SpanJournal(capacity=1 << 17))
    try:
        on_rps = run_tpu_native(batches, args.window_ms,
                                args.checkpoint_every, **kw)[0]
    finally:
        tracing.uninstall()
    snap = journal.snapshot()
    spans = snap["spans"]
    hot = sum(1 for s in spans if s[4] == "hot_stage")
    ckpt = sum(1 for s in spans if s[4] == "checkpoint")
    ratio = on_rps / off_rps if off_rps else 0.0
    return {"journal_snapshot": snap,
            "tracing_off_rps": round(off_rps, 1),
            "tracing_on_rps": round(on_rps, 1),
            "throughput_ratio": round(ratio, 4),
            "spans": len(spans), "dropped_spans": snap["dropped"],
            "hot_stage_spans": hot, "checkpoint_spans": ckpt}


def write_trace_artifact(path: str, trace: dict, latency_ms: dict) -> dict:
    """Write the Perfetto-loadable trace-event JSON: the ON leg's spans
    plus the fire-latency histogram summary (the ``window_fire_ms``
    percentiles) embedded both as an instant event and in ``otherData``.
    Returns the summary that lands in the bench result details."""
    from flink_tpu.observability import tracing

    snap = trace.pop("journal_snapshot")
    events = tracing.to_chrome(snap, pid=0, process_name="bench")
    lat_summary = {k: v for k, v in latency_ms.items()}
    events.append({"name": "latency.window_fire", "cat": "latency",
                   "ph": "i", "s": "g", "pid": 0, "tid": 0,
                   "ts": snap["anchor_wall_us"], "args": lat_summary})
    artifact = {
        "traceEvents": events, "displayTimeUnit": "ms",
        "otherData": {
            "latency_histograms": {"window_fire_ms": lat_summary},
            "tracing_off_rps": trace["tracing_off_rps"],
            "tracing_on_rps": trace["tracing_on_rps"],
            "throughput_ratio": trace["throughput_ratio"],
            "dropped_spans": trace["dropped_spans"]}}
    with open(path, "w") as f:
        json.dump(artifact, f)
    # count only a summary that carries actual samples — a zero-sample
    # dict would let the --check structural gate pass on a vacuous
    # artifact (no windows fired in the timed run)
    n_summaries = 1 if lat_summary.get("samples") else 0
    return {**trace, "latency_summaries": n_summaries, "path": path}


def check_trace_budget(trace: dict, budget: dict,
                       smoke: bool = False) -> list:
    """trace_cpu gate: tracing must stay within the budgeted throughput
    cost (<5% by default), and the artifact must be STRUCTURALLY useful —
    hot-stage phase spans, checkpoint lifecycle spans and at least one
    latency histogram summary, none of it silently truncated away.
    The throughput ratio only gates FULL-size runs: at smoke size the
    fixed per-pass costs (compile, first-fire) dominate and the on/off
    ratio is noise; the structural checks gate unconditionally."""
    viol = []
    floor = budget.get("min_throughput_ratio", 0.95)
    if not smoke and trace["throughput_ratio"] < floor:
        viol.append(f"tracing-on throughput is "
                    f"{trace['throughput_ratio']:.3f}x tracing-off "
                    f"< floor {floor} (tracing must stay ~free)")
    if trace.get("hot_stage_spans", 0) <= 0:
        viol.append("trace contains no hot-stage phase spans")
    if trace.get("checkpoint_spans", 0) <= 0:
        viol.append("trace contains no checkpoint lifecycle spans")
    if trace.get("latency_summaries", 0) < 1:
        viol.append("trace contains no latency histogram summary")
    return viol


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="small fast run")
    ap.add_argument("--records", type=int, default=0)
    ap.add_argument("--keys", type=int, default=1_000_000)
    ap.add_argument("--batch-size", type=int, default=1 << 18)
    ap.add_argument("--window-ms", type=int, default=5000)
    ap.add_argument("--checkpoint-every", type=int, default=16,
                    help="snapshot every N batches inside the timed run")
    ap.add_argument("--emit-tier", default="host",
                    choices=["host", "device"])
    ap.add_argument("--device-sync", default="auto",
                    choices=["auto", "scatter", "deferred"],
                    help="device replica cadence for the host emit tier: "
                         "per-batch scatter, deferred refresh, or "
                         "transport-calibrated auto (utils/transport.py)")
    ap.add_argument("--skip-verify", action="store_true",
                    help="skip the post-run device-vs-mirror download check")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero if the result violates "
                         "BENCH_BUDGET.json (regression gate)")
    ap.add_argument("--pipeline-depth", type=int, default=1,
                    help="hot-path software pipeline depth (0 = serial "
                         "probe->dispatch->mirror; default 1 overlaps the "
                         "hot stage with the driver + device compute)")
    ap.add_argument("--native-shards", type=int, default=0,
                    help="native probe shard count (0 = auto: "
                         "FLINK_TPU_NATIVE_SHARDS or one per core up to 4)")
    ap.add_argument("--profile", metavar="PATH", default=None,
                    help="write the per-phase breakdown (phase_ns, "
                         "phase_bytes, phases_ms) of the winning timed pass "
                         "to PATH as JSON; the device step is additionally "
                         "annotated for jax.profiler traces "
                         "('window_agg.device_step')")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="end-to-end tracing artifact (ISSUE-10): run a "
                         "tracing-off and a tracing-on leg of the headline "
                         "workload and write the ON leg's span journal as "
                         "Chrome trace-event JSON (Perfetto-loadable: "
                         "hot-stage phase spans, checkpoint lifecycle "
                         "spans, latency histogram summary) to PATH; with "
                         "--check the tracing-on/off throughput ratio "
                         "gates against BENCH_BUDGET.json trace_cpu")
    ap.add_argument("--mesh-devices", type=int, default=0, metavar="N",
                    help="run the SHARDED hot path as one logical window "
                         "operator over an N-device mesh (state in "
                         "key-group-range blocks, records routed by an "
                         "on-device all_to_all, probe sharded per device) "
                         "and report records/sec/pod + records/sec/chip + "
                         "the per-shard probe breakdown.  On CPU targets "
                         "the N host devices are forced automatically "
                         "(--xla_force_host_platform_device_count); with "
                         "--check the result gates against the "
                         "BENCH_BUDGET.json mesh_cpu section")
    ap.add_argument("--cep", action="store_true",
                    help="standalone CEP workload: fraud-detection-style "
                         "pattern over the 1M-key stream through the "
                         "vectorized NFA kernel (cep/vectorized.py), "
                         "reporting matches/sec + partials high-water + "
                         "the measured speedup over the interpreted NFA; "
                         "with --check gates against the BENCH_BUDGET.json "
                         "cep_cpu section")
    ap.add_argument("--queryable", action="store_true",
                    help="standalone serving-tier workload (ISSUE-13): "
                         "--qps-clients pooled clients sustain "
                         "--qps-target batched lookups/sec (live + "
                         "checkpoint consistency) over the binary "
                         "columnar wire with client-side key-group "
                         "routing against the running 1M-key window job; "
                         "reports lookups/sec + client p50/p99 + "
                         "server-side serve p50/p99 + replica lag + the "
                         "job's throughput under load; with --check "
                         "gates against BENCH_BUDGET.json queryable_cpu")
    ap.add_argument("--qps-clients", type=int, default=0,
                    help="--queryable client PROCESS count (0 = auto: 4 "
                         "full, 2 smoke) — clients run out-of-process "
                         "like production readers; only the server "
                         "shares the job's process")
    ap.add_argument("--qps-target", type=int, default=150_000,
                    help="--queryable aggregate sustained lookups/sec "
                         "target the client fleet paces itself to (0 = "
                         "unthrottled max-rate mode)")
    ap.add_argument("--qps-batch-keys", type=int, default=1024,
                    help="--queryable keys per batched request")
    ap.add_argument("--_qps-client", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--_qps-host", default="127.0.0.1",
                    help=argparse.SUPPRESS)
    ap.add_argument("--_qps-port", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--_qps-seed", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--_qps-interval-us", type=float, default=0.0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--paging-cap", type=int, default=0,
                    help="also run one cold-key-paging pass (device tier, "
                         "K_cap=N < key count) and report rps + "
                         "resident/spilled occupancy in details.paging")
    ap.add_argument("--config", type=int, default=2, choices=[1, 2, 3, 4, 5],
                    help="BASELINE.md config: 1=WordCount, 2=1M-key "
                         "tumbling (headline, default), 3=sliding "
                         "multi-field, 4=session+Zipf, 5=SQL TUMBLE/HOP")
    ap.add_argument("--checkpoint-interval", type=int, metavar="MS",
                    default=0,
                    help="standalone checkpoint-under-backpressure run: "
                         "trigger checkpoints every MS milliseconds on a "
                         "MiniCluster window job while seeded SlowConsumer"
                         "/SlowDisk chaos injects backpressure; reports "
                         "checkpoint duration + persisted in-flight bytes "
                         "and exits nonzero if a checkpoint misses the "
                         "checkpoint_backpressure budget; also runs the "
                         "incremental-checkpoint leg (ISSUE-16): delta "
                         "bytes vs a full snapshot at 10%% key churn, "
                         "increments-per-base and chain-resolve recovery "
                         "time, gated by checkpoint_incremental (the "
                         "chain-restore digest-equality check is "
                         "unconditional)")
    ap.add_argument("--autoscale", action="store_true",
                    help="standalone reactive-autoscaler run (ISSUE-14): a "
                         "diurnal load-curve source over a keyed window "
                         "job with a fixed per-dequeue consumer cost; the "
                         "ReactiveAutoscaler rescales 2->4 at the peak "
                         "and back after it via unaligned checkpoints "
                         "with channel-state redistribution (no drain); "
                         "reports rescale latency, throughput recovery "
                         "time and records lost/duplicated (must be 0); "
                         "with --check gates against BENCH_BUDGET.json "
                         "rescale_cpu")
    ap.add_argument("--scenario", default="",
                    help="scenario suite (ISSUE-15): run one named "
                         "end-to-end exactly-once application "
                         "(fraud_detection, sessionized_analytics, "
                         "feature_store) or 'all' — the diurnal load "
                         "curve drives the job under the reactive "
                         "autoscaler with nemeses injected at the peak "
                         "and routed queryable readers; the committed "
                         "transactional output must be exactly-once and "
                         "digest-identical to an unfaulted control; with "
                         "--check gates each scenario against its "
                         "BENCH_BUDGET.json scenario_*_cpu section")
    ap.add_argument("--ha-kill", action="store_true",
                    help="coordinator high availability under fire "
                         "(ISSUE-20): run one scenario (default "
                         "fraud_detection; pick with --scenario) under a "
                         "FileHaStore leader lease, kill the leader's "
                         "lease renewal at the diurnal peak while it "
                         "keeps executing as a zombie, and have a "
                         "standby take over at epoch+1, fence the "
                         "zombie's checkpoint completions and 2PC "
                         "commits, and recover the job from the "
                         "HA-store pointer (increment chains included); "
                         "committed output must be exactly-once and "
                         "digest-identical to an unfaulted control; "
                         "with --check gates against BENCH_BUDGET.json "
                         "ha_cpu")
    ap.add_argument("--inject-wedge", action="store_true",
                    help="standalone recovery smoke: wedge the hot-path "
                         "dispatch with a deterministic chaos schedule and "
                         "drive the shared watchdog/quarantine/degrade/"
                         "heal/re-promote path end-to-end; exits nonzero "
                         "if the cycle or digest equality fails")
    args = ap.parse_args()

    if getattr(args, "_qps_client"):
        # hidden worker mode: one out-of-process queryable client of the
        # --queryable bench (never imports jax — stays off the job's GIL)
        sys.exit(_qps_client_main(args))

    import jax
    if (jax.devices()[0].platform == "cpu"
            and not os.environ.get("JAX_PLATFORMS", "").startswith("cpu")):
        sys.exit("bench: JAX found no accelerator and JAX_PLATFORMS=cpu was "
                 "not set; there is no CPU fallback")
    from flink_tpu import native
    if not native.native_available():
        # the numpy mirror would silently stand in for the C hot path
        sys.exit(f"bench: the native layer did not build: "
                 f"{native.build_error()}")

    if args.trace and (args.cep or args.queryable or args.mesh_devices
                       or args.config != 2 or args.inject_wedge
                       or args.checkpoint_interval or args.autoscale
                       or args.scenario or args.ha_kill):
        # --trace measures the HEADLINE single-chip workload's on/off legs;
        # the dedicated-mode branches below exit before the trace block, so
        # refuse loudly instead of silently writing no artifact
        print("# ERROR: --trace applies to the headline bench only; drop "
              "--cep/--queryable/--mesh-devices/--config to produce the "
              "trace artifact", file=sys.stderr)
        sys.exit(2)

    if args.inject_wedge:
        # standalone smoke with its own fixed 1s window: the cycle under
        # test (wedge -> degrade -> heal -> re-promote) is window-size
        # independent, and the headline flags stay untouched
        result = run_wedge_smoke()
        print(json.dumps(result))
        sys.exit(0 if result["ok"] else 1)

    if args.checkpoint_interval:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_BUDGET.json")
        with open(path) as f:
            budgets = json.load(f)
        budget = budgets.get("checkpoint_backpressure", {})
        result = run_checkpoint_backpressure(
            args.checkpoint_interval,
            budget_ms=budget.get("max_duration_ms", 5000.0),
            min_completed=budget.get("min_completed", 1))
        # incremental leg (ISSUE-16): delta bytes vs full at 10% churn,
        # increments-per-base, chain-resolve recovery time, digest gate
        inc = run_incremental_checkpoint_bench(smoke=args.smoke)
        inc_viol = check_incremental_budget(
            inc, budgets.get("checkpoint_incremental", {}),
            smoke=args.smoke)
        result["incremental"] = inc
        result["ok"] = bool(result["ok"] and inc["ok"] and not inc_viol)
        print(json.dumps(result))
        if not result["ok"]:
            print(f"# BUDGET VIOLATION: checkpoint under backpressure — "
                  f"max duration {result['max_duration_ms']} ms vs budget "
                  f"{result['budget_ms']} ms, state {result['state']}, "
                  f"{result['completed_checkpoints']} completed",
                  file=sys.stderr)
        for v in inc_viol:
            print(f"# BUDGET VIOLATION: {v}", file=sys.stderr)
        sys.exit(0 if result["ok"] else 1)

    if args.ha_kill:
        result = run_ha_kill_bench(args)
        print(json.dumps(result))
        print(f"# ha-kill: {json.dumps(result.get('ha_kill', {}))}",
              file=sys.stderr)
        if args.check:
            path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "BENCH_BUDGET.json")
            with open(path) as f:
                budget = json.load(f).get("ha_cpu", {})
            viol = check_ha_budget(result.get("ha_kill", {}), budget,
                                   smoke=args.smoke)
            for v in viol:
                print(f"# BUDGET VIOLATION: {v}", file=sys.stderr)
            sys.exit(1 if viol else 0)
        sys.exit(0 if result.get("ok") else 1)

    if args.scenario:
        result = run_scenario_bench(args)
        print(json.dumps(result))
        for s in result["scenarios"]:
            print(f"# scenario {s['scenario']}: {json.dumps(s)}",
                  file=sys.stderr)
        if args.check:
            from flink_tpu.scenarios import get_scenario
            path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "BENCH_BUDGET.json")
            with open(path) as f:
                budgets = json.load(f)
            viol = []
            for s in result["scenarios"]:
                section = get_scenario(s["scenario"]).budget_section
                viol += check_scenario_budget(s, budgets.get(section, {}),
                                              smoke=args.smoke)
            for v in viol:
                print(f"# BUDGET VIOLATION: {v}", file=sys.stderr)
            sys.exit(1 if viol else 0)
        sys.exit(0 if result["ok"] else 1)

    if args.autoscale:
        result = run_autoscale_bench(args)
        print(json.dumps(result))
        if args.check:
            path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "BENCH_BUDGET.json")
            with open(path) as f:
                budget = json.load(f).get("rescale_cpu", {})
            viol = check_rescale_budget(result, budget, smoke=args.smoke)
            for v in viol:
                print(f"# BUDGET VIOLATION: {v}", file=sys.stderr)
            sys.exit(1 if viol else 0)
        sys.exit(0 if result.get("ok") else 1)

    if args.cep:
        result = run_cep_bench(args)
        print(json.dumps(result))
        print(f"# details: {json.dumps(result.get('details', {}))}",
              file=sys.stderr)
        if args.check:
            path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "BENCH_BUDGET.json")
            with open(path) as f:
                budget = json.load(f).get("cep_cpu", {})
            viol = check_cep_budget(result, budget, smoke=args.smoke)
            for v in viol:
                print(f"# BUDGET VIOLATION: {v}", file=sys.stderr)
            sys.exit(1 if viol else 0)
        sys.exit(0 if result.get("ok") else 1)

    if args.queryable:
        result = run_queryable_bench(args)
        print(json.dumps(result))
        print(f"# details: {json.dumps(result.get('details', {}))}",
              file=sys.stderr)
        if args.check:
            path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "BENCH_BUDGET.json")
            with open(path) as f:
                budget = json.load(f).get("queryable_cpu", {})
            viol = check_queryable_budget(result, budget, smoke=args.smoke)
            for v in viol:
                print(f"# BUDGET VIOLATION: {v}", file=sys.stderr)
            sys.exit(1 if viol else 0)
        sys.exit(0 if result.get("ok") else 1)

    if args.mesh_devices:
        result = run_mesh_bench(args)
        print(json.dumps(result))
        print(f"# details: {json.dumps(result.get('details', {}))}",
              file=sys.stderr)
        if args.check:
            path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "BENCH_BUDGET.json")
            with open(path) as f:
                budgets = json.load(f)
            import jax
            tier = ("mesh_cpu" if jax.devices()[0].platform == "cpu"
                    else "mesh")
            budget = budgets.get(tier)
            if budget is not None and args.smoke:
                # smoke sizes are one batch of fixed costs: the structural
                # checks (shard share, phases, replay) still gate, the
                # full-run pod floor does not
                budget = {k: v for k, v in budget.items()
                          if k != "min_rps_pod"}
            # no budget section for this backend: the correctness checks
            # (restore/replay) still gate — a digest mismatch must never
            # exit 0 just because no perf floor is configured
            viol = check_mesh_budget(result, budget or {})
            for v in viol:
                print(f"# BUDGET VIOLATION: {v}", file=sys.stderr)
            sys.exit(1 if viol else 0)
        sys.exit(0 if result.get("ok") else 1)

    if args.config != 2:
        result = CONFIG_RUNNERS[args.config](args.smoke)
        print(json.dumps(result))
        if args.check:
            path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "BENCH_BUDGET.json")
            with open(path) as f:
                budget = json.load(f).get(f"config{args.config}")
            if budget is not None:
                ok = result["value"] >= budget["min_rps"]
                if not ok:
                    print(f"# BUDGET VIOLATION: rec/s {result['value']:.0f}"
                          f" < floor {budget['min_rps']:.0f}",
                          file=sys.stderr)
                sys.exit(0 if ok else 1)
        return

    n_records = args.records or (1 << 18 if args.smoke else 1 << 24)
    n_keys = min(args.keys, n_records)
    batches = make_batches(n_records, n_keys, args.batch_size, args.window_ms)
    if args.native_shards == 0 and args.emit_tier == "host":
        # measured, not assumed: steal-heavy vCPUs can make the parallel
        # probe counterproductive (see _pick_native_shards)
        args.native_shards = _pick_native_shards()

    (tpu_rps, tpu_fired, snaps, mid, digests, phases, bytes_, _shard_ns,
     op) = run_tpu_native(batches, args.window_ms, args.checkpoint_every,
                          args.emit_tier, args.device_sync,
                          pipeline_depth=args.pipeline_depth,
                          native_shards=args.native_shards)
    replay_ok = replay_check(batches, args.window_ms, mid, digests,
                             args.emit_tier, args.device_sync,
                             pipeline_depth=args.pipeline_depth,
                             native_shards=args.native_shards)
    # device-vs-mirror consistency: a REAL device download of the live
    # panes, compared against the host mirror (post-timing).  Under
    # deferred sync this validates the refresh round trip (upload ->
    # download -> compare); under scatter, continuous equality.
    mirror_ok = True
    if args.emit_tier == "host" and not args.skip_verify:
        mirror_ok = op.verify_mirror()

    # the device tier pays a real download per fire sample: cap the sample
    # count so an explicit --emit-tier device run finishes in minutes
    lat = measure_fire_latency(
        batches, args.window_ms,
        min_samples=(32 if args.smoke else 128)
        if args.emit_tier == "host" else 16,
        max_samples=256 if args.emit_tier == "host" else 16,
        emit_tier=args.emit_tier, device_sync=args.device_sync,
        pipeline_depth=args.pipeline_depth,
        native_shards=args.native_shards)

    # transparency: when the transport calibration sent the headline run
    # down the deferred path, ALSO measure the scatter path (the r1-r3
    # configuration) — single full pass, same warmup/checkpoint cadence —
    # so the cost of per-batch device sync on this link is on the record
    scatter_cmp = None
    if op.device_sync_mode == "deferred" and not args.smoke:
        s_rps, _f, _s, _m, _d, s_phases, s_bytes, _sn, _op2 = run_tpu_native(
            batches, args.window_ms, args.checkpoint_every,
            args.emit_tier, device_sync="scatter", timed_passes=1,
            pipeline_depth=args.pipeline_depth,
            native_shards=args.native_shards)
        s_ns = s_phases.pop("elapsed", 1)
        scatter_cmp = {
            "rps": round(s_rps, 1),
            "phases_ms": {k: round(v / 1e6, 1)
                          for k, v in sorted(s_phases.items())},
            "elapsed_ms": round(s_ns / 1e6, 1),
            "h2d_mb": round(s_bytes.get("h2d", 0) / 1e6, 2),
            "note": "single timed pass (headline gets best-of-3)",
        }

    # best-of-N on BOTH sides: the TPU path takes the max of three passes,
    # so the baselines get the same treatment — a one-sided max would bias
    # vs_baseline upward.  (The heap loop runs under a per-pass time budget,
    # so its rate is robust to a slow window; two passes suffice.)
    base_budget = 3.0 if args.smoke else 15.0
    base_rps = max(run_heap_baseline(batches, args.window_ms, base_budget)[0]
                   for _ in range(2))
    numpy_rps = max(run_numpy_baseline(batches, args.window_ms)[0]
                    for _ in range(3))

    import jax
    platform = jax.devices()[0].platform
    ns = phases.pop("elapsed", 1)
    detail = {
        "phases_ms": {k: round(v / 1e6, 1) for k, v in sorted(phases.items())},
        "elapsed_ms": round(ns / 1e6, 1),
        "h2d_mb": round(bytes_.get("h2d", 0) / 1e6, 2),
        "d2h_mb": round(bytes_.get("d2h", 0) / 1e6, 2),
        "snapshots_in_timed_run": snaps,
        "restore_replay_ok": replay_ok,
        "device_mirror_consistent": mirror_ok,
        "emit_tier": args.emit_tier,
        "windows_fired": tpu_fired,
        "latency_ms": {k: round(v, 2) if isinstance(v, float) else v
                       for k, v in lat.items()},
        "numpy_baseline_rps": round(numpy_rps, 1),
        "heap_baseline_rps": round(base_rps, 1),
        "device_sync": op.device_sync_mode,
        "pipeline_depth": args.pipeline_depth,
        "native_shards": op._nm_shards,
    }
    from flink_tpu.utils import transport
    if transport.dispatch_ms_per_mb() is not None:
        detail["dispatch_ms_per_mb"] = round(transport.dispatch_ms_per_mb(), 2)
    if op.phase_bytes.get("h2d_refresh"):
        # the post-timing verify refresh (deferred sync's sync point)
        detail["h2d_refresh_mb"] = round(
            op.phase_bytes["h2d_refresh"] / 1e6, 2)
    if scatter_cmp is not None:
        detail["scatter_mode"] = scatter_cmp
    if args.paging_cap:
        # cold-key paging pass (state/paging.py): state larger than HBM on
        # the same workload — occupancy proves the ring ran as a cache
        p_rps, p_stats, p_phases = run_paged(
            batches, args.window_ms, args.checkpoint_every, args.paging_cap,
            pipeline_depth=args.pipeline_depth,
            native_shards=args.native_shards)
        detail["paging"] = {
            "rps": round(p_rps, 1),
            "resident_keys": p_stats["resident_keys"],
            "spilled_keys": p_stats["spilled_keys"],
            "evictions": p_stats["evictions"],
            "promotions": p_stats["promotions"],
            "capacity": p_stats["capacity"],
            "spill_mem_mb": round(p_stats["spill_mem_bytes"] / 1e6, 2),
            "spill_log_mb": round(p_stats["spill_log_bytes"] / 1e6, 2),
            "paging_ms": round(p_phases.get("paging", 0) / 1e6, 1),
        }
    trace_detail = None
    if args.trace:
        trace = run_trace_bench(args, batches)
        trace_detail = write_trace_artifact(args.trace, trace,
                                            detail["latency_ms"])
        detail["trace"] = trace_detail
    result = {
        "metric": f"records/sec/chip (1M-key tumbling sum, {platform}, "
                  f"checkpointing every {args.checkpoint_every} batches)",
        "value": round(tpu_rps, 1),
        "unit": "records/sec",
        "p99_fire_latency_ms": round(lat["p99"], 1),
        "latency_samples": lat["samples"],
        "vs_baseline": round(tpu_rps / base_rps, 3),
        "vs_numpy_baseline": round(tpu_rps / numpy_rps, 3),
        "details": detail,
    }
    print(json.dumps(result))
    print(f"# details: {json.dumps(detail)}", file=sys.stderr)
    if args.profile:
        # per-phase artifact (VERDICT #10): raw ns/bytes counters of the
        # WINNING timed pass plus the derived ms view — phase keys are the
        # operator's ``_phase`` names (asserted by tests/test_bench_gate)
        artifact = {
            "phase_ns": {k: int(v) for k, v in sorted(phases.items())},
            "phase_bytes": {k: int(v) for k, v in sorted(bytes_.items())},
            "phases_ms": detail["phases_ms"],
            "elapsed_ms": detail["elapsed_ms"],
            "device_sync": op.device_sync_mode,
            "pipeline_depth": args.pipeline_depth,
            "native_shards": op._nm_shards,
            "trace_annotation": "window_agg.device_step",
        }
        with open(args.profile, "w") as f:
            json.dump(artifact, f, indent=1, sort_keys=True)
        print(f"# profile written: {args.profile}", file=sys.stderr)
    if trace_detail is not None:
        print(f"# trace written: {args.trace} "
              f"({trace_detail['spans']} spans, "
              f"ratio {trace_detail['throughput_ratio']})", file=sys.stderr)
    if args.check:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_BUDGET.json")
        with open(path) as f:
            budgets = json.load(f)
        tier = "smoke" if args.smoke else "full"
        # CPU runs (JAX_PLATFORMS=cpu) gate
        # against their own LOW-water marks — the accelerator floors would
        # always trip on a single CPU core; real-accelerator runs gate
        # against the *_device sections (ROADMAP item 2: device rounds
        # regress loudly, like CPU ones)
        if platform == "cpu" and f"{tier}_cpu" in budgets:
            tier = f"{tier}_cpu"
        elif platform != "cpu" and f"{tier}_device" in budgets:
            tier = f"{tier}_device"
        budget = budgets[tier]
        viol = check_budget(result, budget)
        if trace_detail is not None:
            # tracing-on must cost <5% throughput (trace_cpu section) and
            # the artifact must carry the spans the round needs
            viol += check_trace_budget(trace_detail,
                                       budgets.get("trace_cpu", {}),
                                       smoke=args.smoke)
        for v in viol:
            print(f"# BUDGET VIOLATION: {v}", file=sys.stderr)
        if viol:
            sys.exit(1)
    # correctness gates the exit code with or without --check
    failed = [name for name, ok in (("restore_replay_ok", replay_ok),
                                    ("device_mirror_consistent", mirror_ok))
              if not ok]
    if failed:
        print(f"# CORRECTNESS FAILED: {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
