"""Basic stream operators: map/filter/flatMap, keyBy, timestamps/watermarks,
keyed running reduce, and sinks — all batched.

Analogs: ``StreamMap``/``StreamFilter``/``StreamFlatMap``
(``flink-streaming-java/.../api/operators/``), the keying side of
``KeyedStream.java`` + ``KeyGroupStreamPartitioner``,
``TimestampsAndWatermarksOperator.java``, ``StreamGroupedReduceOperator``.
Each processes a whole ``RecordBatch`` per call; jax-traceable map/filter
bodies fuse into the surrounding device step (operator chaining,
``OperatorChain.java:88`` — on TPU, XLA does the fusing).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flink_tpu.core.batch import RecordBatch, StreamElement, Watermark
from flink_tpu.core.functions import AggregateFunction, RuntimeContext
from flink_tpu.core.watermarks import WatermarkGenerator
from flink_tpu.observability import tracing
from flink_tpu.operators.base import StreamOperator
from flink_tpu.ops.scatter import segment_running_fold
from flink_tpu.state.keyindex import make_key_index


def fire_cause(batch: RecordBatch) -> Dict[str, int]:
    """A window fire's rows carry its ``window_end`` column: a span over
    them shares that identifier with the fire's own spans upstream."""
    end = batch.columns.get("window_end")
    if len(batch) and isinstance(end, np.ndarray) and end.dtype.kind == "i":
        return {"window_end": int(end[0])}
    return {}


class MapOperator(StreamOperator):
    """Vectorized map: fn(columns dict) -> columns dict (row-aligned)."""

    is_stateless = True

    def __init__(self, fn: Callable[[Dict[str, Any]], Dict[str, Any]],
                 name: str = "map"):
        self.fn = fn
        self.name = name

    def process_batch(self, batch: RecordBatch) -> List[StreamElement]:
        return [batch.with_columns(self.fn(dict(batch.columns)))]


class FilterOperator(StreamOperator):
    """Vectorized filter: fn(columns) -> bool mask [B]."""

    is_stateless = True

    def __init__(self, fn: Callable[[Dict[str, Any]], np.ndarray],
                 name: str = "filter"):
        self.fn = fn
        self.name = name

    def process_batch(self, batch: RecordBatch) -> List[StreamElement]:
        mask = np.asarray(self.fn(dict(batch.columns)))
        if mask.all():
            return [batch]
        return [batch.select(mask)]


class FlatMapOperator(StreamOperator):
    """Vectorized flatMap: fn(columns) -> (new_columns, src_row_indices).

    ``src_row_indices`` (int array, len = output rows) says which input row
    produced each output row, so timestamps/keys propagate correctly.
    """

    is_stateless = True

    def __init__(self, fn: Callable[[Dict[str, Any]], Any], name: str = "flat-map"):
        self.fn = fn
        self.name = name

    def process_batch(self, batch: RecordBatch) -> List[StreamElement]:
        cols, src = self.fn(dict(batch.columns))
        src = np.asarray(src)
        ts = None if batch.timestamps is None else np.asarray(batch.timestamps)[src]
        kid = None if batch.key_ids is None else np.asarray(batch.key_ids)[src]
        kg = None if batch.key_groups is None else np.asarray(batch.key_groups)[src]
        return [RecordBatch(cols, ts, kid, kg)]


class KeyByOperator(StreamOperator):
    """Name the key of a batch (``KeyGroupStreamPartitioner`` analog).

    Key groups travel with a batch for a named key column and
    ``max_parallelism``: a batch the exchange already keyed for this
    operator's pair passes through as it is, any other comes out keyed for
    it, and the values are derived at most once a record, by the first
    reader of ``batch.key_groups`` and only if there is one.  They are
    ``key_group = murmur(hash(key)) % max_parallelism`` — the unit both
    network routing and state sharding agree on, so rescaling moves whole
    key-group ranges (``KeyGroupRangeAssignment.java:50-84``).
    Dense per-key slot ids stay owned by the downstream stateful operator.
    """

    is_stateless = True

    def __init__(self, key_column: str, max_parallelism: int = 128,
                 name: str = "key-by"):
        self.key_column = key_column
        self.max_parallelism = max_parallelism
        self.name = name
        #: records that came keyed for this pair with the key groups there
        #: (``carried``), and records whose key this operator named without
        #: deriving anything (``unread``: a later reader derives them)
        self.key_groups_carried = 0
        self.key_groups_unread = 0

    def process_batch(self, batch: RecordBatch) -> List[StreamElement]:
        keyed = batch.keyed_by(self.key_column, self.max_parallelism)
        if keyed is batch and batch.key_groups_derived:
            self.key_groups_carried += len(batch)
        else:
            self.key_groups_unread += len(batch)
        return [keyed]


class TimestampsAndWatermarksOperator(StreamOperator):
    """Extract event timestamps + emit watermarks
    (``TimestampsAndWatermarksOperator.java`` analog, batched: the generator
    sees each batch's timestamp column once)."""

    forwards_watermarks = False  # this operator owns event time downstream

    def __init__(self, generator: WatermarkGenerator,
                 timestamp_column: Optional[str] = None,
                 timestamp_fn: Optional[Callable[[Dict[str, Any]], np.ndarray]] = None,
                 name: str = "timestamps-watermarks"):
        self.generator = generator
        self.timestamp_column = timestamp_column
        self.timestamp_fn = timestamp_fn
        self.name = name

    def process_batch(self, batch: RecordBatch) -> List[StreamElement]:
        if self.timestamp_fn is not None:
            ts = np.asarray(self.timestamp_fn(dict(batch.columns)), np.int64)
        elif self.timestamp_column is not None:
            ts = np.asarray(batch.column(self.timestamp_column), np.int64)
        else:
            ts = batch.timestamps
        out: List[StreamElement] = [batch.with_timestamps(ts)]
        wm = self.generator.on_batch(ts)
        if wm is not None:
            out.append(Watermark(wm))
        return out

    def process_watermark(self, watermark: Watermark) -> List[StreamElement]:
        # Upstream watermarks are ignored — this operator owns event time —
        # EXCEPT MAX_WATERMARK (end of input), which is forwarded so bounded
        # jobs flush (reference: TimestampsAndWatermarksOperator.java
        # processWatermark, which passes only Long.MAX_VALUE through).
        from flink_tpu.core.batch import MAX_WATERMARK
        if watermark.timestamp >= MAX_WATERMARK:
            return [Watermark(MAX_WATERMARK)]
        return []

    def snapshot_state(self) -> Dict[str, Any]:
        # watermark generators carry max-seen-timestamp across restores
        return {"gen": dict(self.generator.__dict__)}

    def restore_state(self, snapshot: Dict[str, Any]) -> None:
        self.generator.__dict__.update(snapshot.get("gen", {}))


class KeyedReduceOperator(StreamOperator):
    """``keyBy().reduce(fn)`` — emits the running per-key fold for EVERY input
    record (``StreamGroupedReduceOperator`` semantics), computed batched:

    sort batch by dense key slot -> segmented inclusive ``associative_scan``
    -> combine each row's in-batch prefix with the key's persisted accumulator
    -> un-sort.  One jitted device step per batch instead of a per-record
    state-map probe (SURVEY §3.3 hot loop (c)).
    """

    def __init__(self, agg: AggregateFunction, key_column: str,
                 value_column: Optional[str] = None,
                 output_column: str = "result",
                 initial_key_capacity: int = 1 << 10,
                 name: str = "keyed-reduce"):
        self.agg = agg
        self.key_column = key_column
        self.value_column = value_column
        self.output_column = output_column
        self.name = name
        self.spec = agg.acc_spec()
        self._K = max(1 << 10, initial_key_capacity)
        self.key_index = None
        self._leaves = None

    def _ensure(self, keys: np.ndarray):
        if self.key_index is None:
            self.key_index = make_key_index(keys[0] if keys.ndim else keys)

    def _alloc(self, K: int):
        return tuple(
            jnp.broadcast_to(jnp.asarray(init, dtype), (K,) + tuple(shape)).copy()
            for init, shape, dtype in zip(self.spec.leaf_inits, self.spec.leaf_shapes,
                                          self.spec.leaf_dtypes))

    @partial(jax.jit, static_argnums=0)
    def _step(self, leaves, slot_ids, values):
        lifted = tuple(jax.tree_util.tree_leaves(self.agg.lift(values)))
        order, sids, is_end, prefix = segment_running_fold(
            slot_ids, lifted, self.agg.combine_leaves)
        K = leaves[0].shape[0]
        current = tuple(l[jnp.minimum(sids, K - 1)] for l in leaves)
        running = self.agg.combine_leaves(current, prefix)
        write_ids = jnp.where(is_end, sids, K)
        new_leaves = tuple(
            l.at[write_ids].set(r.astype(l.dtype), mode="drop")
            for l, r in zip(leaves, running))
        # un-sort the running values back to input row order
        inv = jnp.argsort(order)
        out = self.agg.get_result(self.spec.unflatten(
            tuple(r[inv] for r in running)))
        return new_leaves, out

    def process_batch(self, batch: RecordBatch) -> List[StreamElement]:
        if len(batch) == 0:
            return []
        keys = np.asarray(batch.column(self.key_column))
        self._ensure(keys)
        slot_ids = self.key_index.lookup_or_insert(keys)
        if self._leaves is None:
            self._leaves = self._alloc(self._K)
        while self.key_index.num_keys > self._K:
            newK = self._K * 2
            grown = self._alloc(newK)
            self._leaves = tuple(g.at[: self._K].set(l)
                                 for g, l in zip(grown, self._leaves))
            self._K = newK
        values = (batch.column(self.value_column) if self.value_column
                  else dict(batch.columns))
        # pad to pow2 batch size: variable hash-split batch sizes would
        # otherwise recompile _step per distinct size (static-shape rule).
        # Pad slots use the out-of-range sentinel K -> writes drop.
        B = len(batch)
        Bp = max(64, 1 << (B - 1).bit_length())
        if Bp != B:
            pad = Bp - B
            slot_ids = np.concatenate(
                [np.asarray(slot_ids), np.full(pad, self._K, np.int64)])
            values = jax.tree_util.tree_map(
                lambda a: np.concatenate(
                    [np.asarray(a),
                     np.zeros((pad,) + np.shape(a)[1:], np.asarray(a).dtype)]),
                values)
        self._leaves, out = self._step(self._leaves,
                                       jnp.asarray(slot_ids, jnp.int32), values)
        out = jax.tree_util.tree_map(lambda a: np.asarray(a)[:B], out)
        cols = dict(batch.columns)
        if isinstance(out, dict):
            cols.update(out)
        else:
            cols[self.output_column] = out
        return [batch.with_columns(cols)]

    def snapshot_state(self) -> Dict[str, Any]:
        if self.key_index is None:
            return {"empty": True}
        return {
            "empty": False,
            "keys": self.key_index.snapshot(),
            "key_index_kind": type(self.key_index).__name__,
            "leaves": [np.asarray(l)[: self.key_index.num_keys]
                       for l in self._leaves],
        }

    def restore_state(self, snap: Dict[str, Any]) -> None:
        from flink_tpu.state.keyindex import KeyIndex, ObjectKeyIndex
        if snap.get("empty", True):
            return
        cls = (ObjectKeyIndex if snap["key_index_kind"] == "ObjectKeyIndex"
               else KeyIndex)
        self.key_index = cls.restore(snap["keys"])
        while self._K < self.key_index.num_keys:
            self._K *= 2
        self._leaves = self._alloc(self._K)
        n = snap["leaves"][0].shape[0]
        self._leaves = tuple(l.at[:n].set(jnp.asarray(s))
                             for l, s in zip(self._leaves, snap["leaves"]))


class SideOutputOperator(StreamOperator):
    """Consumes one side output tag (``DataStream.getSideOutput`` analog):
    unwraps matching TaggedBatch elements, drops the main stream."""

    def __init__(self, tag: str, name: str = "side-output"):
        self.accepts_tag = tag
        self.name = name

    def process_batch(self, batch: RecordBatch) -> List[StreamElement]:
        return []  # main-stream data does not pass

    def process_tagged(self, batch: RecordBatch) -> List[StreamElement]:
        return [batch]


class SinkOperator(StreamOperator):
    """Terminal operator wrapping a sink function (``StreamSink`` analog)."""

    #: the span ``process_batch`` opens (a chain counts it under that name)
    span = "sink.invoke"

    def __init__(self, sink, name: str = "sink"):
        import copy as _copy

        # transactional/stateful sinks declare clone_per_subtask: each
        # parallel operator instance needs its OWN epoch buffers and txn
        # identity (a shared instance races across subtask threads and
        # breaks barrier alignment); collection-style sinks stay shared
        if getattr(sink, "clone_per_subtask", False):
            sink = _copy.deepcopy(sink)
            on_cloned = getattr(sink, "on_cloned", None)
            if on_cloned is not None:
                on_cloned()
        self.sink = sink
        self.name = name

    def open(self, ctx: RuntimeContext) -> None:
        super().open(ctx)
        if hasattr(self.sink, "open"):
            self.sink.open(ctx)

    def process_batch(self, batch: RecordBatch) -> List[StreamElement]:
        with tracing.span(self.span, cat="sink", records=len(batch),
                          **fire_cause(batch)):
            self.sink.write_batch(batch)
        return []

    def process_watermark(self, watermark: Watermark) -> List[StreamElement]:
        if hasattr(self.sink, "on_watermark"):
            self.sink.on_watermark(watermark.timestamp)
        return []

    def on_latency_marker(self, marker) -> None:
        """Source→sink latency sample (``LatencyStats`` at the sink).
        Reads through the clock seam so ClockSkew chaos covers latency
        tracking; skew-negative samples clamp to 0."""
        from flink_tpu.utils import clock

        self.latencies_ms = getattr(self, "latencies_ms", [])
        self.latencies_ms.append(max(
            0.0, (clock.now_ms_f() / 1000.0 - marker.marked_time) * 1000.0))
        if len(self.latencies_ms) > 1024:
            del self.latencies_ms[:512]

    def end_input(self) -> List[StreamElement]:
        # transactional sinks finalize on end-of-stream (commit the last
        # epoch's transaction — TwoPhaseCommitSink.end_input); without
        # this the tail between the final barrier and end-of-input stays
        # staged forever and close() ABORTS it: committed-output loss on
        # every bounded job (found gating the scenario suite, ISSUE-15)
        if hasattr(self.sink, "end_input"):
            self.sink.end_input()
        elif hasattr(self.sink, "flush"):
            self.sink.flush()
        return []

    # two-phase-commit sinks (FileSink/LogSink) hook the checkpoint lifecycle
    def snapshot_state(self) -> Dict[str, Any]:
        if hasattr(self.sink, "snapshot_state"):
            return self.sink.snapshot_state()
        return {}

    def restore_state(self, snapshot: Dict[str, Any]) -> None:
        if snapshot and hasattr(self.sink, "restore_state"):
            self.sink.restore_state(snapshot)

    def notify_checkpoint_complete(self, checkpoint_id: int) -> None:
        if hasattr(self.sink, "notify_checkpoint_complete"):
            self.sink.notify_checkpoint_complete(checkpoint_id)

    def close(self) -> None:
        if hasattr(self.sink, "close"):
            self.sink.close()


class ExtremumByOperator(StreamOperator):
    """``KeyedStream.minBy/maxBy`` analog: per key, keep the FULL ROW of the
    extreme element seen so far (ties keep the first arrival, the
    reference's ``minBy(field, first=true)``), emitting the current extreme
    per touched key per micro-batch with the TRIGGERING record's timestamp
    (``StreamGroupedReduceOperator`` emission semantics).  State follows the
    repo keyed-snapshot convention (key index + slot-aligned row fields) so
    rescale split/merge redistributes it by key group."""

    def __init__(self, key_column: str, value_column: str, is_min: bool,
                 name: str = "extremum-by"):
        self.key_column = key_column
        self.value_column = value_column
        self.is_min = is_min
        self.name = name
        self.key_index = None
        self._vals = np.zeros(0, np.float64)   # slot -> extreme value
        self._rows = np.zeros(0, object)       # slot -> extreme row dict

    def _ensure(self, n: int) -> None:
        if n > self._vals.size:
            cap = max(n, max(16, self._vals.size * 2))
            sentinel = np.inf if self.is_min else -np.inf
            nv = np.full(cap, sentinel, np.float64)
            nv[: self._vals.size] = self._vals
            nr = np.empty(cap, object)
            nr[: self._rows.size] = self._rows
            self._vals, self._rows = nv, nr

    def process_batch(self, batch: RecordBatch) -> List[StreamElement]:
        from flink_tpu.state.keyindex import make_key_index

        if len(batch) == 0:
            return []
        # NaN values can never win (a stored NaN would poison strict
        # comparisons forever); rows carrying NaN are ignored entirely
        vals_all = np.asarray(batch.column(self.value_column), np.float64)
        finite = ~np.isnan(vals_all)
        if not finite.all():
            batch = batch.select(finite)
            if len(batch) == 0:
                return []
        n = len(batch)
        keys = np.asarray(batch.column(self.key_column))
        vals = np.asarray(batch.column(self.value_column), np.float64)
        ts = (np.asarray(batch.timestamps)
              if batch.timestamps is not None else None)
        if self.key_index is None:
            self.key_index = make_key_index(keys[0] if keys.ndim else keys)
        slots = self.key_index.lookup_or_insert(keys).astype(np.int64)
        self._ensure(self.key_index.num_keys)
        _uniq, inv = np.unique(slots, return_inverse=True)
        # batch-local extreme per key: lexsort by (key group, value,
        # arrival) — the first row of each group is the winner
        sort_vals = vals if self.is_min else -vals
        order = np.lexsort((np.arange(n), sort_vals, inv))
        first = np.ones(n, bool)
        first[1:] = inv[order][1:] != inv[order][:-1]
        winners = order[first]
        rows = batch.take(winners).to_rows()
        out_rows: List[Dict[str, Any]] = []
        out_ts: List[int] = []
        better = (lambda a, b: a < b) if self.is_min else (lambda a, b: a > b)
        for row, w in zip(rows, winners.tolist()):
            slot = int(slots[w])
            v = float(vals[w])
            if self._rows[slot] is None or better(v, self._vals[slot]):
                self._vals[slot] = v
                self._rows[slot] = row
            out_rows.append(self._rows[slot])
            # emission carries the TRIGGERING record's timestamp: the
            # stored extreme may be arbitrarily behind the watermark
            out_ts.append(int(ts[w]) if ts is not None else 0)
        out = RecordBatch.from_rows(
            out_rows, timestamps=out_ts if ts is not None else None)
        return [out]

    def snapshot_state(self) -> Dict[str, Any]:
        if self.key_index is None:
            return {"empty": True}
        n = self.key_index.num_keys
        return {"empty": False,
                "keys": self.key_index.snapshot(),
                "key_index_kind": type(self.key_index).__name__,
                "state.vals": self._vals[:n].copy(),
                "state.rows": self._rows[:n].copy()}

    def restore_state(self, snap: Dict[str, Any]) -> None:
        from flink_tpu.state.keyindex import KeyIndex, ObjectKeyIndex

        if snap.get("empty", True):
            return
        cls = (ObjectKeyIndex if snap["key_index_kind"] == "ObjectKeyIndex"
               else KeyIndex)
        self.key_index = cls.restore(snap["keys"])
        n = self.key_index.num_keys
        self._ensure(n)
        self._vals[:n] = np.asarray(snap["state.vals"])
        self._rows[:n] = np.asarray(snap["state.rows"], object)
