"""A task thread's account of its own time (ISSUE-37): the three gauges
mean their names (``backpressure_ns`` is blocked puts only, ``busy_ns``
everything else that is no wait for input, the three cover the loop), the
thread's CPU clock is read from outside it, and a task whose operator is
no chain still keeps one counter set."""

import threading
import time

import numpy as np
import pytest

from flink_tpu.cluster.channels import LocalChannel, OutputDispatcher
from flink_tpu.cluster.task import Subtask, TaskListener
from flink_tpu.core import keygroups
from flink_tpu.core.batch import EndOfInput, RecordBatch
from flink_tpu.core.functions import RuntimeContext
from flink_tpu.observability import SpanJournal, tracing
from flink_tpu.operators.base import StreamOperator


@pytest.fixture(autouse=True)
def _clean_journal():
    tracing.uninstall()
    yield
    tracing.uninstall()


class _Pass(StreamOperator):
    """Hands every batch on; ``spin_s`` of CPU work a batch, through the
    device-health lane when ``guarded``."""

    name = "pass"

    def __init__(self, spin_s=0.0, guarded=False):
        self.spin_s = spin_s
        self.guarded = guarded

    def _spin(self):
        end = time.perf_counter() + self.spin_s
        while time.perf_counter() < end:
            pass

    def process_batch(self, batch):
        if self.guarded:
            from flink_tpu.runtime import device_health

            device_health.guarded_dispatch(self._spin, label="test.spin")
        elif self.spin_s:
            self._spin()
        return [batch]


def _batch(n=64):
    return RecordBatch({"k": np.arange(n, dtype=np.int64),
                        "v": np.ones(n, np.float32)})


def _task(op, outputs, capacity=64):
    ch = LocalChannel(capacity, name="in")
    task = Subtask("v1", 0, op, outputs, RuntimeContext(), TaskListener(),
                   [ch])
    return task, ch


def _loop_wall_ns(task):
    return task._loop_end_ns - task._loop_t0_ns


def test_a_slow_partition_into_free_channels_is_busy_not_backpressure(
        monkeypatch):
    """The hash edge's own work (here 20 ms a batch) is the thread being
    busy: it is in `busy_ns`, and no put ever blocked, so
    `backpressure_ns` is 0."""
    real = keygroups.rows_by_target

    def slow(*args, **kwargs):
        time.sleep(0.02)
        return real(*args, **kwargs)

    monkeypatch.setattr(keygroups, "rows_by_target", slow)
    outs = [LocalChannel(64, name=f"out{i}") for i in range(2)]
    task, ch = _task(_Pass(), [OutputDispatcher("hash", outs,
                                                key_column="k")])
    task.start()
    for _ in range(5):
        ch.put(_batch())
    ch.put(EndOfInput())
    task.join()
    assert task.state == "FINISHED"
    assert sum(len(o) for o in outs) >= 10          # both targets got parts
    assert task.backpressure_ns == 0
    assert task.busy_ns >= 5 * 20_000_000
    assert task.cpu_ns < task.busy_ns // 2              # it slept
    assert task.busy_ns + task.idle_ns == _loop_wall_ns(task)


def test_a_full_channel_is_backpressure_and_nothing_else():
    """One output channel of capacity 1 that nobody reads for 80 ms: the
    time the task's puts were blocked is what `backpressure_ns` holds,
    equal to the channel's own gauge, and it is not busy time."""
    out = LocalChannel(1, name="out")
    task, ch = _task(_Pass(), [OutputDispatcher("forward", [out])])
    task.start()
    for _ in range(3):
        ch.put(_batch())
    ch.put(EndOfInput())
    time.sleep(0.08)
    drained = []

    def drain():
        while not drained or not isinstance(drained[-1], EndOfInput):
            el = out.poll(timeout_s=0.01)
            if el is not None:
                drained.append(el)

    reader = threading.Thread(target=drain, daemon=True)
    reader.start()
    task.join()
    reader.join(timeout=10.0)
    assert task.state == "FINISHED" and len(drained) == 4
    assert task.backpressure_ns == out.backpressured_ns >= 60_000_000
    wall = _loop_wall_ns(task)
    assert task.busy_ns + task.idle_ns + task.backpressure_ns == wall
    assert task.busy_ns < wall - 60_000_000


def test_busy_idle_and_backpressure_cover_the_threads_wall_time():
    """Between two reads of a running task, busy + idle + backpressure
    grows by the wall time between them (to 5 %), whatever the thread
    did: waited for input, worked, was blocked."""
    out = LocalChannel(2, name="out")
    task, ch = _task(_Pass(spin_s=0.002),
                     [OutputDispatcher("forward", [out])])
    task.start()
    feeding = threading.Event()

    def feed():
        for i in range(150):
            ch.put(_batch())
            if i % 3 == 0:
                out.poll(timeout_s=0.0)     # sometimes room, sometimes not
            time.sleep(0.002)
        feeding.set()

    feeder = threading.Thread(target=feed, daemon=True)
    feeder.start()

    def read():
        return (time.monotonic_ns(),
                task.busy_ns + task.idle_ns + task.backpressure_ns)

    time.sleep(0.03)
    # one reading of the clock serves all three: they sum to the wall time
    # whenever they are read (read apart, busy would run ahead)
    sums = [sum(task.loop_times_ns()) for _ in range(200)]
    assert sums == sorted(sums) and all(
        min(task.loop_times_ns()) >= 0 for _ in range(200))
    (t_a, sum_a) = read()
    time.sleep(0.4)
    (t_b, sum_b) = read()
    assert 0.95 * (t_b - t_a) <= sum_b - sum_a <= 1.05 * (t_b - t_a)
    feeding.wait(timeout=30.0)
    task.cancel()
    task.join()
    assert task.idle_ns > 0 and task.busy_ns > 0
    total = task.busy_ns + task.idle_ns + task.backpressure_ns
    assert 0.95 * _loop_wall_ns(task) <= total <= _loop_wall_ns(task)


def test_cpu_ns_is_read_from_outside_and_grows_under_load():
    """`Task.cpu_ns`: the task thread's CPU clock, read by another thread;
    it grows while the thread spins, hardly at all while it waits for
    input, never passes the wall time, and keeps its last value once the
    thread is gone.  `thread_cpu_ns()` names the dispatch lane beside
    the task thread."""
    out = LocalChannel(1024, name="out")
    task, ch = _task(_Pass(spin_s=0.01, guarded=True),
                     [OutputDispatcher("forward", [out])])
    assert task.cpu_ns == 0 and task.thread_cpu_ns() == {}
    t0 = time.monotonic_ns()
    task.start()
    time.sleep(0.1)
    waiting = task.cpu_ns
    for _ in range(10):
        ch.put(_batch())
    while len(out) < 10:
        time.sleep(0.005)
    threads = task.thread_cpu_ns()
    loaded = task.cpu_ns
    wall = time.monotonic_ns() - t0
    # the spin ran on the lane thread: 10 x 10 ms of CPU there
    lane, = [name for name in threads if name.startswith("device-lane")]
    assert threads[lane] >= 30_000_000
    assert threads[task._thread.name] <= loaded <= wall
    assert waiting < 50_000_000 and waiting <= loaded
    ch.put(EndOfInput())
    task.join()
    assert task.state == "FINISHED"
    assert loaded <= task.cpu_ns <= time.monotonic_ns() - t0
    assert task.cpu_ns == task.cpu_ns            # frozen with the thread


def test_a_task_thread_that_spins_uses_the_cpu_it_is_busy_for():
    out = LocalChannel(1024, name="out")
    task, ch = _task(_Pass(spin_s=0.01),
                     [OutputDispatcher("forward", [out])])
    task.start()
    for _ in range(10):
        ch.put(_batch())
    ch.put(EndOfInput())
    task.join()
    assert task.cpu_ns >= 30_000_000
    assert task.cpu_ns <= task.busy_ns + task.idle_ns + task.backpressure_ns


def test_an_operator_that_is_no_chain_reports_one_entry():
    """The task meters a bare operator as a chain meters a member: span
    `chain.<name>` (with `records=`) and one counter set."""
    j = tracing.install(SpanJournal(1 << 10))
    out = LocalChannel(64, name="out")
    task, ch = _task(_Pass(), [OutputDispatcher("forward", [out])])
    task.start()
    for _ in range(3):
        ch.put(_batch(32))
    ch.put(EndOfInput())
    task.join()
    stats = task.chain_stats
    assert list(stats) == ["chain.pass"]
    assert (stats["chain.pass"]["batches"], stats["chain.pass"]["rows"]) \
        == (3, 96)
    assert 0 < stats["chain.pass"]["cpu_ns"] <= stats["chain.pass"]["ns"] \
        <= task.busy_ns
    spans = [s for s in j.spans() if s[3] == "chain.pass"]
    assert [(s[4], s[6]) for s in spans] == [("chain", {"records": 32})] * 3
