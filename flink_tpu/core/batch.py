"""Stream elements, batched.

The reference moves one ``StreamElement`` at a time through the dataflow
(records, watermarks, barriers, latency markers — see
``flink-streaming-java/.../streamrecord/``).  The TPU-native unit of flow is a
**columnar RecordBatch** (dense numpy/jax arrays, one device micro-step per
batch); control elements (``Watermark``, ``CheckpointBarrier``,
``LatencyMarker``, ``StreamStatus``) stay individual and flow *in order*
between batches — boundary-exactness for checkpoints falls out of that
ordering exactly as it does from the reference's in-band barriers
(``SingleCheckpointBarrierHandler.java:194``).
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any, Dict, Iterable, List, Mapping, Optional, Tuple

import numpy as np

from flink_tpu.core import keygroups

LONG_MIN = -(2 ** 63)
LONG_MAX = 2 ** 63 - 1

#: Watermark value meaning "end of stream" (reference: Watermark.MAX_WATERMARK)
MAX_WATERMARK = LONG_MAX


class StreamElement:
    __slots__ = ()

    def is_batch(self) -> bool:
        return False


@dataclass(frozen=True)
class Watermark(StreamElement):
    """Event-time watermark: no element with ts <= this will arrive later."""

    timestamp: int

    def is_batch(self) -> bool:
        return False


@dataclass(frozen=True)
class StreamStatus(StreamElement):
    """Channel idleness marker (``StreamStatus`` analog): idle channels are
    excluded from watermark alignment."""

    idle: bool


@dataclass(frozen=True)
class LatencyMarker(StreamElement):
    """Latency-tracking probe (``LatencyMarker.java:32``): flows through
    operators without entering user functions; every hop records
    marked_time→now (``observability/latency.py``), sinks included.
    ``source`` names the emitting vertex so per-(source, hop) histograms
    attribute samples without an id registry."""

    marked_time: float
    source_id: int = 0
    subtask_index: int = 0
    source: str = ""


@dataclass(frozen=True)
class CheckpointBarrier(StreamElement):
    """In-band checkpoint barrier (``CheckpointBarrier.java``)."""

    checkpoint_id: int
    timestamp: int
    is_savepoint: bool = False


@dataclass(frozen=True)
class EndOfInput(StreamElement):
    """End of a bounded stream."""


@dataclass(frozen=True)
class OutputTag:
    """Names a side output (``OutputTag`` analog)."""

    name: str


class TaggedBatch(StreamElement):
    """A batch destined for a side output: routed only to the matching
    ``SideOutputOperator`` (``ProcessOperator`` side-output emission analog);
    every other consumer drops it."""

    __slots__ = ("tag", "batch")

    def __init__(self, tag: str, batch: "RecordBatch"):
        self.tag = tag
        self.batch = batch


class RecordBatch(StreamElement):
    """Columnar record batch.

    columns:    name -> array [B, ...] (numpy on host, jax on device paths)
    timestamps: int64[B] event timestamps in ms, or None (no time semantics yet)
    key_ids:    int32[B] dense key-slot ids (present after keying), or None
    key_groups: int32[B] key-group per record (present after keying), or None
    key_spec:   ``(key_column, max_parallelism)`` the key groups are those
                of, or None (not keyed, or key groups handed in as an array)

    Key groups travel with a batch for a named key column and
    ``max_parallelism``; they are derived at most once a record, by the
    first read of ``key_groups``, and only if somebody reads them.  The
    values are Flink's ``KeyGroupRangeAssignment``:
    ``murmur_hash(hash_keys(key)) % max_parallelism``.

    "At most once" holds inside one process: ``native/codec.py`` ships the
    name of a named key and not its key groups, so a reader in another
    process derives the same values again.  The key column of a keyed
    batch is not written in place: ``with_columns`` tells a replaced key
    column by its identity (any other array under the key's name, a copy
    included, derives the old key's groups first), and a write into the
    array itself goes unseen.
    """

    __slots__ = ("columns", "timestamps", "key_ids", "_key_groups",
                 "key_spec", "_size")

    def __init__(self, columns: Mapping[str, Any], timestamps=None,
                 key_ids=None, key_groups=None,
                 key_spec: Optional[Tuple[str, int]] = None):
        self.columns: Dict[str, Any] = dict(columns)
        self.timestamps = timestamps
        self.key_ids = key_ids
        self._key_groups = key_groups
        self.key_spec = key_spec
        if self.columns:
            first = next(iter(self.columns.values()))
            self._size = int(np.shape(first)[0])
        elif timestamps is not None:
            self._size = int(np.shape(timestamps)[0])
        else:
            self._size = 0
        # Row-alignment invariant: a size-changing map that keeps stale
        # timestamps/key_ids would silently attribute rows to wrong keys.
        for attr, v in (("timestamps", timestamps), ("key_ids", key_ids),
                        ("key_groups", key_groups)):
            if v is not None and int(np.shape(v)[0]) != self._size:
                raise ValueError(
                    f"{attr} length {int(np.shape(v)[0])} != batch size {self._size}")
        for n, v in self.columns.items():
            if int(np.shape(v)[0]) != self._size:
                raise ValueError(
                    f"column {n!r} length {int(np.shape(v)[0])} != batch size {self._size}")

    def is_batch(self) -> bool:
        return True

    def __len__(self) -> int:
        return self._size

    @property
    def size(self) -> int:
        return self._size

    def column(self, name: str):
        return self.columns[name]

    @property
    def key_groups(self):
        """The key group of every record, derived from ``key_spec``'s
        column on the first read and kept."""
        kg = self._key_groups
        if kg is None and self.key_spec is not None:
            key_column, max_parallelism = self.key_spec
            kg = self._key_groups = keygroups.assign_to_key_group(
                keygroups.hash_keys(np.asarray(self.columns[key_column])),
                max_parallelism)
        return kg

    @property
    def key_groups_derived(self) -> bool:
        """Whether ``key_groups`` is there to read without deriving it."""
        return self._key_groups is not None

    def keyed_by(self, key_column: str, max_parallelism: int) -> "RecordBatch":
        """This batch keyed on ``key_column``: itself when it carries key
        groups for that column and ``max_parallelism`` already, else a
        batch that derives them on the first read of ``key_groups``."""
        spec = (key_column, int(max_parallelism))
        if self.key_spec == spec:
            return self
        if key_column not in self.columns:
            raise KeyError(key_column)
        return RecordBatch(self.columns, self.timestamps, self.key_ids,
                           None, spec)

    def with_columns(self, columns: Mapping[str, Any]) -> "RecordBatch":
        spec = self.key_spec
        if spec is not None and columns.get(spec[0]) is not self.columns[spec[0]]:
            # the key column is replaced: the records keep the key groups
            # of the key they were keyed by, which no column names any more
            return RecordBatch(columns, self.timestamps, self.key_ids,
                               self.key_groups)
        return RecordBatch(columns, self.timestamps, self.key_ids,
                           self._key_groups, spec)

    def with_keys(self, key_ids, key_groups=None) -> "RecordBatch":
        """New dense key ids.  Key groups handed in replace the batch's own,
        under no key's name; without them the batch keeps what it carries
        (derived or not)."""
        if key_groups is not None:
            return RecordBatch(self.columns, self.timestamps, key_ids,
                               key_groups)
        return RecordBatch(self.columns, self.timestamps, key_ids,
                           self._key_groups, self.key_spec)

    def with_timestamps(self, timestamps) -> "RecordBatch":
        return RecordBatch(self.columns, timestamps, self.key_ids,
                           self._key_groups, self.key_spec)

    def select(self, mask: np.ndarray) -> "RecordBatch":
        """Host-side row filter by boolean mask."""
        return self.take(mask)

    def take(self, indices: np.ndarray) -> "RecordBatch":
        """The rows at ``indices``, in that order."""
        def rows(v):
            return None if v is None else np.asarray(v)[indices]

        return RecordBatch({k: rows(v) for k, v in self.columns.items()},
                           rows(self.timestamps), rows(self.key_ids),
                           rows(self._key_groups), self.key_spec)

    @staticmethod
    def concat(batches: Iterable["RecordBatch"]) -> "RecordBatch":
        all_batches = list(batches)
        batches = [b for b in all_batches if len(b)]
        if not batches:
            # Preserve schema/keyed-ness of an all-empty flush so downstream
            # presence checks (timestamps/key_ids is not None) stay stable.
            return all_batches[0] if all_batches else RecordBatch({})
        if len(batches) == 1:
            return batches[0]
        first = batches[0]
        names = set(first.columns)

        def keyed(b):
            return b.key_spec is not None or b._key_groups is not None

        for b in batches[1:]:
            if set(b.columns) != names:
                raise ValueError(f"concat of heterogeneous batches: {sorted(names)} vs {sorted(b.columns)}")
            for attr in ("timestamps", "key_ids"):
                if (getattr(b, attr) is None) != (getattr(first, attr) is None):
                    raise ValueError(f"concat of batches with inconsistent {attr} presence")
            if keyed(b) != keyed(first):
                raise ValueError("concat of batches with inconsistent key_groups presence")
        cols = {n: np.concatenate([np.asarray(b.columns[n]) for b in batches]) for n in first.columns}
        ts = (np.concatenate([np.asarray(b.timestamps) for b in batches])
              if first.timestamps is not None else None)
        kid = (np.concatenate([np.asarray(b.key_ids) for b in batches])
               if first.key_ids is not None else None)
        # key groups nobody has read stay unread when every part names the
        # same key; one part that holds them makes the others derive theirs
        spec = first.key_spec
        if any(b.key_spec != spec for b in batches):
            spec = None
        kg = None
        if keyed(first) and (
                spec is None or any(b._key_groups is not None for b in batches)):
            kg = np.concatenate([np.asarray(b.key_groups) for b in batches])
        return RecordBatch(cols, ts, kid, kg, spec)

    # -- pickling (unaligned checkpoints persist queued batches) ------------
    def __getstate__(self):
        return {"columns": self.columns, "timestamps": self.timestamps,
                "key_ids": self.key_ids, "key_groups": self._key_groups,
                "key_spec": self.key_spec, "_size": self._size}

    def __setstate__(self, state):
        if isinstance(state, tuple):   # the slots form older snapshots hold
            state = state[1]
        self.columns = state["columns"]
        self.timestamps = state.get("timestamps")
        self.key_ids = state.get("key_ids")
        self._key_groups = state.get("key_groups")
        self.key_spec = state.get("key_spec")
        self._size = state["_size"]

    @staticmethod
    def from_rows(rows: List[Mapping[str, Any]], timestamps: Optional[List[int]] = None) -> "RecordBatch":
        """Test/connector convenience: list of dict rows -> columnar batch."""
        if not rows:
            return RecordBatch({})
        names = rows[0].keys()
        cols = {n: np.asarray([r[n] for r in rows]) for n in names}
        ts = np.asarray(timestamps, np.int64) if timestamps is not None else None
        return RecordBatch(cols, ts)

    def to_rows(self) -> List[Dict[str, Any]]:
        arrs = {k: np.asarray(v) for k, v in self.columns.items()}

        def cell(a, i):
            x = a[i]
            if isinstance(x, np.generic):
                return x.item()
            return x  # object cells (strings) or sub-arrays pass through

        return [{k: cell(a, i) for k, a in arrs.items()} for i in range(self._size)]

    def __repr__(self) -> str:
        cols = {k: f"{np.asarray(v).dtype}{list(np.shape(v))}" for k, v in self.columns.items()}
        return f"RecordBatch(n={self._size}, cols={cols}, keyed={self.key_ids is not None})"
