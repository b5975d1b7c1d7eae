"""The stamping sink: keeps every delivered batch as numpy columns with the
wall time at which it had the rows in hand, and opens the measured window
when the last warm-up window has arrived whole."""

from __future__ import annotations

import threading
import time

import jax
import numpy as np

from flink_tpu.connectors.sinks import Sink


class StampSink(Sink):
    """One instance, shared by the sink operators of every window subtask."""

    def __init__(self, sync_window_end: int, sync_rows, open_window):
        self._lock = threading.Lock()
        #: (stamp, {column: ndarray}) per delivered batch, in arrival order
        self.batches = []
        self._sync_end = sync_window_end
        self._sync_rows = sync_rows         # callable: rows that window owes
        self._sync_seen = 0
        self._open_window = open_window

    def write_batch(self, batch) -> None:
        with jax.profiler.TraceAnnotation("bench.sink.write"):
            # a sink has the rows when they are host memory it can read
            cols = {k: np.asarray(v) for k, v in batch.columns.items()}
            stamp = time.monotonic()
        with self._lock:
            self.batches.append((stamp, cols))
            if self._open_window is None:
                return
            self._sync_seen += int(
                np.count_nonzero(cols["window_end"] == self._sync_end))
            if self._sync_seen and self._sync_seen >= self._sync_rows():
                self._open_window()
                self._open_window = None
