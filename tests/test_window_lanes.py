"""Every fold lane of the window operator against ONE plain reference.

The window operator folds a batch one way (``_hot_stage_fold``); what still
varies is where the folded state lives and who serves a fire.  Each of
those lanes — device tier; host tier with scatter sync; host tier
deferred; host tier on the numpy mirror; device tier pipelined; the
two-device mesh on the device tier — runs two jobs (5 s tumbling f32 sum;
60 s / 5 s sliding sum/count/min/max) over an in-order and an out-of-order
stream (allowed lateness, late side output) and must deliver the rows, the
late rows, the drop counter and the mid-stream snapshot's cells of
:class:`Reference`: a dict keyed by (key, pane) and Python loops, no
operator code.  Values are multiples of 1/8, so every f32 sum is exact and
the comparison is equality on every lane.

The restore cases cut the out-of-order stream mid-way in lane A, restore the
snapshot into a fresh operator of lane B and run to the end: everything
delivered after the cut must again be the reference's.
"""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

from flink_tpu.core.batch import RecordBatch, TaggedBatch, Watermark
from flink_tpu.core.functions import (CountAggregator, MaxAggregator,
                                      MinAggregator, RuntimeContext,
                                      SumAggregator, TupleAggregator)
from flink_tpu.operators.window_agg import WindowAggOperator
from flink_tpu.windowing.assigners import (SlidingEventTimeWindows,
                                           TumblingEventTimeWindows)

SLIDE_MS = 5000            # pane width of both jobs
LATENESS_MS = 5000         # out-of-order stream only
OUT_OF_ORDERNESS_MS = 2000
N_BATCHES = 18
BATCH = 240
CUT = 9                    # the snapshot follows this batch's watermark
LATE_TAG = "late"

#: job -> (window size ms, result fields or None for the plain sum)
JOBS = {
    "tumbling_sum": (5000, None),
    "sliding_multiagg": (60000, ("sum", "count", "min", "max")),
}

LANES = {
    "device": dict(emit_tier="device"),
    "host_scatter": dict(emit_tier="host", device_sync="scatter"),
    "host_deferred": dict(emit_tier="host", device_sync="deferred"),
    "host_numpy": dict(emit_tier="host", device_sync="scatter",
                       native_emit=False),
    "device_pipelined": dict(emit_tier="device", pipeline_depth=2),
    "mesh2_device": dict(emit_tier="device", mesh=2),
}
ONE_DEVICE_LANES = ("device", "host_scatter", "host_deferred", "host_numpy")
#: the mesh operator on one device: a restore case's other side only
RESTORE_LANES = {**LANES, "mesh1_device": dict(emit_tier="device", mesh=1)}

#: ``snapshot_state()`` of a one-device lane holding state, as the parent
#: commit (16c61ea) writes it
PARENT_SNAPSHOT_KEYS = {
    "pane_base", "max_pane", "last_fired_window", "watermark",
    "late_dropped", "P", "key_index", "key_index_kind", "panes", "leaves",
    "counts", "leaf_schema"}


# ---------------------------------------------------------------------------
# the stream
# ---------------------------------------------------------------------------

def make_stream(job: str, ordered: bool, seed: int = 7):
    """[(keys i64, values f32, timestamps i64, watermark after the batch)].
    One batch per 5 s of event time; the distinct keys grow past the
    operators' initial capacity.  Out of order: rows shuffled, one in seven
    2-7 s behind (inside the lateness of an already fired window), one in
    twenty further behind than its last window's cleanup time."""
    size, _ = JOBS[job]
    rng = np.random.default_rng(seed)
    too_late = size + LATENESS_MS + 2 * SLIDE_MS
    out = []
    for i in range(N_BATCHES):
        keys = rng.integers(0, 40 + 12 * i, BATCH).astype(np.int64) * 7919 + 3
        vals = (rng.integers(-32, 96, BATCH) / 8.0).astype(np.float32)
        ts = i * SLIDE_MS + rng.integers(0, SLIDE_MS, BATCH).astype(np.int64)
        if ordered:
            ts.sort()
            wm = int(ts.max()) - 1
        else:
            kind = rng.random(BATCH)
            ts = ts - np.where(kind < 1 / 7,
                               rng.integers(2000, 2000 + LATENESS_MS, BATCH),
                               0)
            ts = ts - np.where(kind > 0.95, too_late, 0)
            keep = ts >= 0
            keys, vals, ts = keys[keep], vals[keep], ts[keep]
            wm = (i + 1) * SLIDE_MS - 1 - OUT_OF_ORDERNESS_MS
        out.append((keys, vals, ts, wm))
    return out


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

ACC = ("sum", "count", "min", "max")     # a cell, in this order


class Reference:
    """Event-time windows over panes of ``SLIDE_MS``, as the operator's
    docstring states them: a record is late when the cleanup time of the
    last window covering its pane (end - 1 + lateness) is at or behind the
    watermark, and then goes to the side output; a window fires every key
    it holds when the watermark passes its end - 1, fires again at once
    for each batch that touches it while its own cleanup time is ahead,
    and a pane goes once its last window's cleanup time is passed."""

    def __init__(self, size: int, fields, lateness: int):
        self.size, self.fields, self.lateness = size, fields, lateness
        self.cells = {}           # (key, pane start) -> [sum, count, min, max]
        self.wm = -(1 << 63)
        self.rows = []            # (window start, key, *values)
        self.late = []            # (key, value, timestamp)

    def _window_starts(self, pane: int):
        return range(pane - self.size + SLIDE_MS, pane + 1, SLIDE_MS)

    def _passed(self, start: int, wm: int) -> bool:
        return start + self.size - 1 <= wm

    def _open(self, start: int) -> bool:
        return start + self.size - 1 + self.lateness > self.wm

    def _fire(self, start: int) -> None:
        acc = {}
        for (key, pane), (s, c, lo, hi) in self.cells.items():
            if start <= pane < start + self.size:
                a = acc.setdefault(key, [0.0, 0, np.inf, -np.inf])
                a[0] += s
                a[1] += c
                a[2] = min(a[2], lo)
                a[3] = max(a[3], hi)
        for key, cell in acc.items():
            vals = dict(zip(ACC, cell))
            self.rows.append((start, key) + tuple(
                vals[f] for f in (self.fields or ("sum",))))

    def batch(self, keys, vals, ts) -> None:
        touched = set()
        for k, v, t in zip(keys.tolist(), vals.tolist(), ts.tolist()):
            pane = t - t % SLIDE_MS
            if not self._open(pane):    # its last window starts at the pane
                self.late.append((k, v, t))
                continue
            c = self.cells.setdefault((k, pane), [0.0, 0, np.inf, -np.inf])
            c[0] += v
            c[1] += 1
            c[2] = min(c[2], v)
            c[3] = max(c[3], v)
            touched.add(pane)
        again = {s for p in touched for s in self._window_starts(p)
                 if self._passed(s, self.wm) and self._open(s)}
        for start in sorted(again):
            self._fire(start)

    def watermark(self, wm: int) -> None:
        if wm <= self.wm:
            return
        starts = {s for (_k, p) in self.cells for s in self._window_starts(p)
                  if not self._passed(s, self.wm) and self._passed(s, wm)}
        for start in sorted(starts):
            self._fire(start)
        self.wm = wm
        self.cells = {(k, p): c for (k, p), c in self.cells.items()
                      if self._open(p)}

    def live_cells(self):
        """{(key, pane start): (count, *leaves in the snapshot's order)}"""
        order = sorted(self.fields or ("sum",))
        out = {}
        for kp, cell in self.cells.items():
            vals = dict(zip(ACC, cell))
            out[kp] = (vals["count"],) + tuple(vals[f] for f in order)
        return out


@functools.lru_cache(maxsize=None)
def reference(job: str, ordered: bool):
    """What every lane must deliver: ``whole`` for the uncut run, ``tail``
    for what follows the cut, ``cells`` at the cut."""
    size, fields = JOBS[job]
    ref = Reference(size, fields, 0 if ordered else LATENESS_MS)
    mark = None
    for i, (keys, vals, ts, wm) in enumerate(make_stream(job, ordered)):
        ref.batch(keys, vals, ts)
        ref.watermark(wm)
        if i == CUT:
            cells = ref.live_cells()
            mark = (len(ref.rows), len(ref.late))
    ref.watermark(1 << 62)
    return {"whole": (sorted(ref.rows), sorted(ref.late)),
            "tail": (sorted(ref.rows[mark[0]:]), sorted(ref.late[mark[1]:])),
            "cells": cells}


# ---------------------------------------------------------------------------
# driver and digests
# ---------------------------------------------------------------------------

def make_op(job: str, lane: str, ordered: bool):
    size, fields = JOBS[job]
    if fields is None:
        assigner = TumblingEventTimeWindows.of(size)
        agg = dict(agg=SumAggregator(jnp.float32), value_column="v")
    else:
        make = {"sum": lambda: SumAggregator(jnp.float32),
                "count": CountAggregator,
                "min": lambda: MinAggregator(jnp.float32),
                "max": lambda: MaxAggregator(jnp.float32)}
        assigner = SlidingEventTimeWindows.of(size, SLIDE_MS)
        agg = dict(agg=TupleAggregator({f: ("v", make[f]()) for f in fields}),
                   value_selector=lambda c: c)
    kw = dict(RESTORE_LANES[lane], key_column="k", initial_key_capacity=64,
              **agg)
    if not ordered:
        kw.update(allowed_lateness_ms=LATENESS_MS, late_output_tag=LATE_TAG)
    n_mesh = kw.pop("mesh", 0)
    if n_mesh:
        from flink_tpu.parallel.mesh import make_mesh
        from flink_tpu.parallel.mesh_runtime import MeshWindowAggOperator
        op = MeshWindowAggOperator(assigner, mesh=make_mesh(n_mesh), **kw)
    else:
        op = WindowAggOperator(assigner, **kw)
    op.open(RuntimeContext(max_parallelism=128))
    return op


def drive(op, stream, first: int = 0, cut=None):
    """Batches ``first..`` of ``stream`` through ``op``; returns the
    elements it delivered and, with ``cut``, the snapshot taken after that
    batch's watermark (the run then stops there)."""
    out = []
    for i, (keys, vals, ts, wm) in enumerate(stream):
        if i < first:
            continue
        out += op.process_batch(RecordBatch({"k": keys, "v": vals},
                                            timestamps=ts))
        out += op.process_watermark(Watermark(wm))
        if i == cut:
            out += op.prepare_snapshot_pre_barrier()
            return out, op.snapshot_state()
    out += op.end_input()
    return out, None


def digest(job: str, elements):
    """(sorted rows, sorted late rows) in the reference's form."""
    _, fields = JOBS[job]
    rows, late = [], []
    for e in elements:
        if isinstance(e, TaggedBatch):
            assert e.tag == LATE_TAG
            b = e.batch
            late += zip(np.asarray(b.column("k")).tolist(),
                        np.asarray(b.column("v")).tolist(),
                        np.asarray(b.timestamps).tolist())
        elif isinstance(e, RecordBatch):
            cols = [np.asarray(e.column(c)).tolist()
                    for c in ("window_start", "k") + (fields or ("result",))]
            rows += zip(*cols)
    return sorted(rows), sorted(late)


def snapshot_cells(snap):
    """{(key, pane start): (count, *leaves)} of a snapshot's filled cells."""
    from flink_tpu.state.shard_layout import densify_keyed_snapshot
    snap = densify_keyed_snapshot(snap)
    keys = np.asarray(snap["key_index"]["reverse"])
    counts = np.asarray(snap["counts"])
    out = {}
    for row, col in zip(*np.nonzero(counts)):
        out[int(keys[row]), int(snap["panes"][col]) * SLIDE_MS] = (
            int(counts[row, col]),) + tuple(
                np.asarray(leaf)[row, col].item() for leaf in snap["leaves"])
    return out


# ---------------------------------------------------------------------------
# every lane against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ordered", [True, False],
                         ids=["in_order", "out_of_order"])
@pytest.mark.parametrize("job", list(JOBS))
@pytest.mark.parametrize("lane", list(LANES))
def test_lane_delivers_the_reference(lane, job, ordered):
    ref = reference(job, ordered)
    stream = make_stream(job, ordered)
    op = make_op(job, lane, ordered)
    head, snap = drive(op, stream, cut=CUT)
    assert snapshot_cells(snap) == ref["cells"]
    tail, _ = drive(op, stream, first=CUT + 1)
    rows, late = digest(job, head + tail)
    assert rows == ref["whole"][0]
    assert late == ref["whole"][1]
    assert op.late_dropped == 0   # the late rows went to the side output
    if not ordered:
        # the stream exercises what it claims to
        assert late and len(rows) > len(set(r[:2] for r in rows))
    if op.emit_tier == "host":
        assert op.verify_mirror()
    op.close()


# ---------------------------------------------------------------------------
# a snapshot of lane A restored into lane B
# ---------------------------------------------------------------------------

def _restore_pairs():
    pairs = [("tumbling_sum", a, b) for a in ONE_DEVICE_LANES
             for b in ONE_DEVICE_LANES if a != b]
    pairs += [("sliding_multiagg", a, a) for a in LANES]
    pairs += [("sliding_multiagg", "mesh1_device", "mesh2_device"),
              ("sliding_multiagg", "mesh2_device", "mesh1_device")]
    return pairs


@pytest.mark.parametrize("job,lane_a,lane_b", _restore_pairs())
def test_snapshot_restores_across_lanes(job, lane_a, lane_b):
    ref = reference(job, False)
    stream = make_stream(job, False)
    a = make_op(job, lane_a, False)
    _, snap = drive(a, stream, cut=CUT)
    a.close()
    if lane_a in ONE_DEVICE_LANES:
        assert set(snap) == PARENT_SNAPSHOT_KEYS
    assert snapshot_cells(snap) == ref["cells"]
    b = make_op(job, lane_b, False)
    b.restore_state(snap)
    tail, _ = drive(b, stream, first=CUT + 1)
    assert digest(job, tail) == ref["tail"]
    assert b.late_dropped == 0
    b.close()
