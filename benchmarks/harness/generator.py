"""The load generator: one general `Source`, driven by a traffic file.

`Stream` is the pure part: batch `b` of a run is a function of the
configuration, the traffic file and `(seed, b)` alone, so the reference
regenerates exactly what the job was sent.  `TrafficSource` is the part with
a clock: it replays the stream's warm-up unpaced, waits for the harness to
open the measured window, then sends either as fast as backpressure admits
(`backlog`) or on a fixed schedule that never slows when the job does
(`paced`, open loop).

Event-time layout, in slides of the configuration's assigner:

    slide 0                 every key of the universe once
    slides 1 .. thin        one batch per source split each (the panes a
                            sliding window needs before its fire is full size)
    the last `warmup_slides` slides of the warm-up, and every measured slide
                            the cell's own traffic at its own density

The measured stream starts on the slide boundary `base_ms`.  The last warm-up
batch of each split ends with one event stamped `base_ms`, so the watermark
passes the last warm-up window and its fire is seen before the window opens.
"""

from __future__ import annotations

import threading
import time

import jax
import numpy as np

from flink_tpu.connectors.sources import Source, SourceSplit
from flink_tpu.core.batch import RecordBatch, Watermark


#: unpaced splits replay in step: none runs more than this many of its own
#: batches ahead of the slowest (see `TrafficSource._ahead`)
MAX_SPLIT_DRIFT_BATCHES = 8


def _require(cond, msg: str) -> None:
    if not cond:
        raise ValueError(f"benchmark traffic: {msg}")


def make_universe(seed: int, n_keys: int) -> np.ndarray:
    """`n_keys` distinct int64 keys drawn sparse from [1, 2^62), sorted (so
    `searchsorted` maps a key back to its index)."""
    rng = np.random.default_rng([seed, 0])
    universe = np.unique(rng.integers(1, 1 << 62, n_keys, dtype=np.int64))
    while universe.size < n_keys:       # 2^20 draws from 2^62: ~never
        universe = np.unique(np.concatenate(
            [universe, rng.integers(1, 1 << 62, n_keys, dtype=np.int64)]
        ))[:n_keys]
    return universe


class Stream:
    """The events of one run as a function of `(seed, batch index)`."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.seed = int(seed) % (1 << 63)
        self.n_keys = int(config["keys"]["count"])
        self.batch = int(config["batch_events"])
        self.splits = int(config["parallelism"])
        assigner = config["assigner"]
        self.size_ms = int(assigner["size_ms"])
        self.slide_ms = int(assigner.get("slide_ms", self.size_ms))
        _require(self.size_ms % self.slide_ms == 0,
                 "window size must be a whole number of slides")
        self.panes = self.size_ms // self.slide_ms

        keys = traffic["keys"]
        _require(keys["distribution"] == "uniform",
                 f"key distribution {keys['distribution']!r} not implemented")
        late = traffic["lateness"]
        _require(late["share"] == 0 and late["bound_ms"] == 0,
                 "late events are not implemented")
        _require(traffic["burst"] == "steady",
                 f"burst shape {traffic['burst']!r} not implemented")
        self.mode = traffic["mode"]
        _require(self.mode in ("backlog", "paced"),
                 f"unknown mode {self.mode!r}")
        if self.mode == "paced":
            self.rate = int(traffic["rate_events_per_s"])
            per_slide = self.rate * self.slide_ms
            _require(per_slide % 1000 == 0, "rate gives a fractional slide")
            self.events_per_slide = per_slide // 1000
        else:
            self.rate = None
            self.events_per_slide = int(traffic["events_per_slide"])
        _require(self.events_per_slide % self.batch == 0
                 and self.n_keys % self.batch == 0,
                 "a slide and the universe must be whole numbers of batches")
        self.batches_per_slide = self.events_per_slide // self.batch

        # warm-up plan: (slide, first event of the batch in its slide,
        # events in that slide, universe pass?) for every warm-up batch
        dense = int(traffic["warmup_slides"])
        warm_slides = max(1 + dense, self.panes + 1)
        plan = []
        for slide in range(warm_slides):
            if slide == 0:
                n_batches, universe = self.n_keys // self.batch, True
            elif slide < warm_slides - dense:
                n_batches, universe = self.splits, False
            else:
                n_batches, universe = self.batches_per_slide, False
            plan += [(slide, q * self.batch, n_batches * self.batch, universe)
                     for q in range(n_batches)]
        _require(len(plan) >= self.splits, "warm-up shorter than the splits")
        self._plan = plan
        self.warm_batches = len(plan)
        #: the first batch of the last warm-up slide, and the batch half way
        #: through it: where the warm-up's two checkpoints are asked for
        self.cut_at_boundary = self.warm_batches - self.batches_per_slide
        self.cut_mid_slide = self.warm_batches - self.batches_per_slide // 2
        self.base_ms = warm_slides * self.slide_ms
        self.universe = make_universe(self.seed, self.n_keys)
        self._pass_order = np.random.default_rng(
            [self.seed, 1]).permutation(self.n_keys)

    def columns(self, b: int):
        """Batch `b`: (`kidx` into the universe, f32 values, int64 event
        times in ms, sorted).  Keys are `universe[kidx]`."""
        rng = np.random.default_rng([self.seed, 2, b])
        offsets = np.arange(self.batch, dtype=np.int64)
        if b < self.warm_batches:
            slide, first, in_slide, universe = self._plan[b]
            if universe:
                kidx = self._pass_order[first:first + self.batch]
            else:
                kidx = rng.integers(0, self.n_keys, self.batch)
            ts = slide * self.slide_ms \
                + (first + offsets) * self.slide_ms // in_slide
            if b >= self.warm_batches - self.splits:
                ts[-1] = self.base_ms      # carries the watermark past warm-up
        else:
            kidx = rng.integers(0, self.n_keys, self.batch)
            first = (b - self.warm_batches) * self.batch
            ts = self.base_ms \
                + (first + offsets) * self.slide_ms // self.events_per_slide
        return kidx, rng.random(self.batch, dtype=np.float32), ts


class RunClock:
    """What the generator, the sink and the harness share about one run:
    when the measured window opened and closes, and what was sent when."""

    def __init__(self, seconds: float, started: float):
        self.seconds = float(seconds)
        self.started = started          # time.monotonic() at process start
        self.t0 = None
        self.t_end = None
        self._open = threading.Event()
        self.at_open = []               # callables run once, at t0
        #: asks the cluster for a checkpoint and returns its id, or None
        #: while an earlier one is still in flight.  The generator calls it
        #: twice in the last warm-up slide, with the state at full size
        #: (`TrafficSource.read_split`); the harness sets it.
        self.request_cut = lambda: 0

    def open_window(self) -> None:
        """Called once, by the sink, when the last warm-up fire is whole."""
        if self._open.is_set():
            return
        self.t0 = time.monotonic()
        self.t_end = self.t0 + self.seconds
        for fn in self.at_open:
            fn()
        self._open.set()

    def is_open(self) -> bool:
        return self._open.is_set()

    def wait_open(self, timeout: float = 1200.0) -> None:
        if not self._open.wait(timeout):
            raise RuntimeError("benchmark: the warm-up never finished")


class TrafficSource(Source):
    """`Stream` + a clock, as a source with one split per source task.

    Whenever a split has to wait (for the window to open, for a slower
    split) it does not block: each turn it hands its source task a watermark
    that the task's own timestamps operator swallows, so the task keeps
    serving its command queue.  A checkpoint asked for while one split
    waits and another sends would otherwise never get the waiting split's
    barrier, and its alignment would hold back the rows everyone waits for.
    """

    bounded = True
    _TICK = Watermark(-(1 << 62))

    def __init__(self, stream: Stream, clock: RunClock):
        self.stream = stream
        self.clock = clock
        #: per split: rows of (batch, due, sent, handed) on time.monotonic();
        #: `handed` is when the source task came back for the next batch
        self.log = [[] for _ in range(stream.splits)]
        #: per split, the last batch index it sent (for `_ahead`)
        self._at = [0] * stream.splits
        #: set once the warm-up's first checkpoint has been asked for
        self._boundary_cut = False
        #: per split, which keys the last warm-up window saw (the sink waits
        #: for exactly that many rows before it opens the measured window)
        self._seen = [np.zeros(stream.n_keys, bool)
                      for _ in range(stream.splits)]

    def sync_rows(self) -> int:
        """Rows the last warm-up window has to deliver."""
        seen = self._seen[0].copy()
        for other in self._seen[1:]:
            seen |= other
        return int(seen.sum())

    def create_splits(self, parallelism: int):
        _require(parallelism == self.stream.splits,
                 f"job runs {parallelism} source tasks, configuration says "
                 f"{self.stream.splits}")
        return [SourceSplit(self, i, parallelism) for i in range(parallelism)]

    def _record_batch(self, b: int, split: int) -> RecordBatch:
        st = self.stream
        with jax.profiler.TraceAnnotation("bench.source.next"):
            kidx, v, ts = st.columns(b)
            if b < st.warm_batches:
                in_sync = (ts >= st.base_ms - st.size_ms) & (ts < st.base_ms)
                self._seen[split][kidx[in_sync]] = True
            return RecordBatch({"k": st.universe[kidx], "v": v, "ts": ts})

    def _ahead(self, index: int, b: int) -> bool:
        """Unpaced splits replay in step: none runs more than
        `MAX_SPLIT_DRIFT_BATCHES` of its own batches ahead of the slowest
        (what watermark alignment does for a partitioned log).  Left alone
        they drift apart by seconds of event time, the job's live panes and
        every shape that follows from them wander, and no two runs do the
        same work."""
        others = [at for i, at in enumerate(self._at) if i != index]
        return bool(others) and b - min(others) \
            > MAX_SPLIT_DRIFT_BATCHES * self.stream.splits

    def _cut(self):
        """Ask for a checkpoint, waiting (without blocking the task) while
        an earlier one is in flight."""
        asked = time.monotonic()
        while self.clock.request_cut() is None:
            if time.monotonic() - asked > 120.0:
                raise RuntimeError("benchmark: a warm-up checkpoint was "
                                   "never admitted")
            yield self._TICK
            time.sleep(0.002)

    def read_split(self, index: int, of: int):
        """The warm-up takes two checkpoints with the state at full size, so
        that the snapshot path has met, before the window opens, both counts
        of live panes a cut can find (a checkpoint reads the live panes, one
        compiled program per count).  The first is cut while exactly one
        split has crossed into the last warm-up slide: that split asks for
        it and then sends its first batch of the slide, the others take
        their barrier at the boundary, so the cut holds one pane more than
        a window.  The second comes half way through the slide, where the
        live panes are a window's."""
        st, clock, log = self.stream, self.clock, self.log[index]
        crosses_first = st.cut_at_boundary % of
        b = index
        while b < st.warm_batches:
            while self._ahead(index, b):
                yield self._TICK
                time.sleep(0.001)
            if b >= st.cut_at_boundary and index != crosses_first \
                    and not self._boundary_cut:
                while not self._boundary_cut:
                    yield self._TICK
                    time.sleep(0.001)
                # one more turn, so this task takes its barrier before it
                # sends a batch of the new slide
                yield self._TICK
            if b == st.cut_at_boundary:
                yield from self._cut()
                self._boundary_cut = True
            elif b == st.cut_mid_slide:
                yield from self._cut()
            self._at[index] = b
            yield self._record_batch(b, index)
            b += of
        # parked until the sink has the last warm-up window whole
        parked = time.monotonic()
        while not clock.is_open():
            if time.monotonic() - parked > 900.0:
                raise RuntimeError("benchmark: the warm-up never finished")
            yield self._TICK
            time.sleep(0.002)
        if st.mode == "paced":
            interval = st.batch / st.rate
            # keep the schedule a few batches past the close, so the last
            # window due inside it still fires under load
            stop = clock.t_end + 4 * interval
            while True:
                due = clock.t0 + (b - st.warm_batches + 1) * interval
                if due > stop:
                    return
                batch = self._record_batch(b, index)
                wait = due - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                row = [b, due, time.monotonic(), None]
                log.append(row)
                yield batch
                row[3] = time.monotonic()
                b += of
        while time.monotonic() < clock.t_end:
            if self._ahead(index, b):
                yield self._TICK
                time.sleep(0.001)
                continue
            batch = self._record_batch(b, index)
            row = [b, None, time.monotonic(), None]
            log.append(row)
            self._at[index] = b
            yield batch
            row[3] = time.monotonic()
            b += of

    def sent_batches(self):
        """Every batch index the job was sent, ascending."""
        st = self.stream
        sent = list(range(st.warm_batches))
        for rows in self.log:
            sent += [r[0] for r in rows]
        return sorted(sent)
