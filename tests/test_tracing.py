"""End-to-end tracing + latency tracking (ISSUE-10).

Tier-1 coverage of the observability subsystem: the span journal's
ordering/overflow semantics, Chrome trace-event export, the
``metrics.latency.interval`` marker→histogram plumbing (job_status,
Prometheus exposition and the REST latency panel in the SAME run), and
the ProcessCluster cross-worker merged timeline.
"""

import json
import sys
import textwrap
import threading
import time
import urllib.request

import numpy as np
import pytest

from flink_tpu.config.config_option import Configuration
from flink_tpu.config.options import MetricOptions
from flink_tpu.core.batch import LatencyMarker
from flink_tpu.metrics.core import Histogram, Meter
from flink_tpu.metrics.groups import MetricRegistry
from flink_tpu.metrics.reporters import PrometheusReporter
from flink_tpu.observability import LatencyTracker, SpanJournal, tracing
from flink_tpu.observability.assembly import (estimate_offset_ms,
                                              merge_timelines)


@pytest.fixture(autouse=True)
def _clean_journal():
    """Tracing is a process-global singleton: every test starts and ends
    without one installed, no matter what it does in between."""
    tracing.uninstall()
    yield
    tracing.uninstall()


# ---------------------------------------------------------------------------
# span journal
# ---------------------------------------------------------------------------

def test_span_ordering_and_kinds():
    j = tracing.install(SpanJournal(64))
    with tracing.span("outer", cat="test", k=1):
        tracing.instant("mark", cat="test")
        with tracing.span("inner", cat="test"):
            pass
    spans = j.spans()
    names = [s[3] for s in spans]
    # completion order: instants record immediately, spans on exit
    assert names == ["mark", "inner", "outer"]
    by_name = {s[3]: s for s in spans}
    assert by_name["mark"][0] == "i" and by_name["outer"][0] == "X"
    # the outer span STARTED before the instant and lasted past inner
    assert by_name["outer"][1] <= by_name["mark"][1]
    assert by_name["outer"][2] >= by_name["inner"][2]
    assert by_name["outer"][6] == {"k": 1}


def test_ring_overflow_drop_counter():
    j = tracing.install(SpanJournal(4))
    for i in range(10):
        tracing.instant(f"e{i}", cat="test")
    assert j.recorded == 4
    assert j.dropped == 6
    # the ring keeps the EARLIEST spans (drop-newest): trace start intact
    assert [s[3] for s in j.spans()] == ["e0", "e1", "e2", "e3"]
    assert j.summary()["categories"] == {"test": 4}


def test_ring_concurrent_reservation_exact():
    """The lock-free reservation (one atomic ``itertools.count`` next())
    stays exact under concurrent recorders: recorded + dropped equals the
    total emitted, the ring fills completely, and every reserved slot got
    its writer's span."""
    j = tracing.install(SpanJournal(10_000))
    n_threads, per = 8, 5_000

    def work():
        for _ in range(per):
            tracing.instant("e", cat="test")

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert j.recorded + j.dropped == n_threads * per
    assert j.recorded == 10_000 and j.dropped == 30_000
    assert all(s is not None for s in j._buf)


def test_adopted_journal_survives_cluster_runs():
    """A journal installed by an outer harness (bench --trace, a user's
    big ring) is ADOPTED by a tracing-enabled cluster, not owned: the
    cluster records into it but must never reset() it — the owner's
    accumulated spans and capacity choice survive the job."""
    from flink_tpu.cluster.minicluster import MiniCluster
    from flink_tpu.datastream.api import StreamExecutionEnvironment

    j = tracing.install(SpanJournal(8192))
    tracing.instant("harness-span", cat="test")
    env = StreamExecutionEnvironment()
    n = 30_000
    (env.from_collection(columns={"k": np.arange(n) % 3,
                                  "v": np.ones(n)}, batch_size=128)
        .key_by("k").sum("v").collect())
    plan = env.get_stream_graph("adopt-job").to_plan()
    mc = MiniCluster(checkpoint_interval_ms=10, tracing_enabled=True)
    assert mc._trace_journal is j and not mc._owns_trace_journal
    res = mc.execute(plan, timeout_s=60)
    assert res.state == "FINISHED"
    assert "harness-span" in {s[3] for s in j.spans()}, \
        "cluster reset an adopted journal"
    # with no journal pre-installed the cluster installs its OWN ring
    # (config capacity applies) and THAT one is reset per execution
    tracing.uninstall()
    mc2 = MiniCluster(checkpoint_interval_ms=10, tracing_enabled=True)
    assert mc2._owns_trace_journal and tracing.active() is mc2._trace_journal
    res2 = mc2.execute(plan, timeout_s=60)
    assert res2.state == "FINISHED"
    # an OWNED ring is released at execution end: the singleton is free,
    # the handle still serves job_status()/trace_events(), and the next
    # tracing-enabled cluster installs fresh instead of adopting (and
    # reporting) job B's spans as its own
    assert tracing.active() is None
    assert mc2._trace_journal.recorded > 0
    assert mc2.job_status()["trace"]["spans"] > 0
    mc3 = MiniCluster(tracing_enabled=True)
    assert mc3._owns_trace_journal
    assert mc3._trace_journal is not mc2._trace_journal


def test_adopting_cluster_recovers_after_owner_release():
    """Two tracing-enabled clusters constructed back to back: B adopts
    A's ring.  After A's execute releases the singleton, B must stand up
    its OWN fresh ring at execute time — never run trace-dead while
    reporting A's stale spans as its own."""
    from flink_tpu.cluster.minicluster import MiniCluster
    from flink_tpu.datastream.api import StreamExecutionEnvironment

    def make_plan(name):
        env = StreamExecutionEnvironment()
        (env.from_collection(columns={"k": np.arange(30_000) % 3,
                                      "v": np.ones(30_000)},
                             batch_size=128)
            .key_by("k").sum("v").collect())
        return env.get_stream_graph(name).to_plan()

    a = MiniCluster(checkpoint_interval_ms=10, tracing_enabled=True)
    b = MiniCluster(checkpoint_interval_ms=10, tracing_enabled=True)
    assert a._owns_trace_journal and not b._owns_trace_journal
    assert b._trace_journal is a._trace_journal
    assert a.execute(make_plan("job-a"), timeout_s=60).state == "FINISHED"
    assert tracing.active() is None          # A released its ring
    assert b.execute(make_plan("job-b"), timeout_s=60).state == "FINISHED"
    assert b._owns_trace_journal
    assert b._trace_journal is not a._trace_journal
    assert b.job_status()["trace"]["spans"] > 0
    assert tracing.active() is None          # B released its ring too


def test_owner_readopts_foreign_ring_at_execute():
    """An OWNING cluster whose singleton was taken over by a DIFFERENT
    owner between executions re-adopts the live ring at execute() — its
    own ring is no longer where instrumentation records, so reporting
    from it would serve the previous execution's spans as the new job's."""
    from flink_tpu.cluster.minicluster import MiniCluster
    from flink_tpu.datastream.api import StreamExecutionEnvironment

    def make_plan(name):
        env = StreamExecutionEnvironment()
        (env.from_collection(columns={"k": np.arange(30_000) % 3,
                                      "v": np.ones(30_000)},
                             batch_size=128)
            .key_by("k").sum("v").collect())
        return env.get_stream_graph(name).to_plan()

    mc = MiniCluster(checkpoint_interval_ms=10, tracing_enabled=True)
    assert mc._owns_trace_journal
    own = mc._trace_journal
    assert mc.execute(make_plan("job-a"), timeout_s=60).state == "FINISHED"
    assert tracing.active() is None and own.recorded > 0
    # an outer harness installs ITS journal between the two executions
    harness = tracing.install(SpanJournal(1 << 15))
    assert mc.execute(make_plan("job-b"), timeout_s=60).state == "FINISHED"
    # job B's spans landed in the harness ring and the cluster reports it
    assert mc._trace_journal is harness and not mc._owns_trace_journal
    assert harness.recorded > 0
    assert mc.job_status()["trace"]["spans"] == harness.recorded
    # adopted, so NOT released: the harness keeps the singleton
    assert tracing.active() is harness


def test_disabled_tracing_is_a_noop():
    assert not tracing.enabled()
    with tracing.span("nope", cat="test"):
        tracing.instant("nor-this")
    tracing.complete("neither", 0, 10)
    assert tracing.active() is None


def test_chrome_export_schema():
    j = tracing.install(SpanJournal(64))
    with tracing.span("work", cat="hot_stage", batch=3):
        pass
    tracing.instant("tick", cat="checkpoint")
    events = tracing.to_chrome(j.snapshot(), pid=7, process_name="p7")
    json.dumps(events)                       # wire-serializable
    meta = [e for e in events if e["ph"] == "M"]
    assert {"process_name", "thread_name"} <= {e["name"] for e in meta}
    x = next(e for e in events if e["ph"] == "X")
    assert x["name"] == "work" and x["cat"] == "hot_stage"
    assert x["pid"] == 7 and "dur" in x and x["ts"] > 0
    i = next(e for e in events if e["ph"] == "i")
    assert i["name"] == "tick" and i["s"] == "t"
    # wall anchoring: ts is microseconds since the epoch, roughly now
    assert abs(x["ts"] / 1e6 - time.time()) < 3600


def test_clock_offset_estimation_and_merge():
    # worker clock 250ms ahead; symmetric RTT -> exact recovery
    assert estimate_offset_ms(1000.0, 1010.0, 1255.0) == 250.0
    j = tracing.install(SpanJournal(16))
    tracing.instant("local", cat="test")
    local = j.snapshot()
    worker_j = SpanJournal(16)
    worker_j.record("i", worker_j.anchor_perf_ns, 0, "remote", "test", None)
    dump = {"journal": worker_j.snapshot(),
            "wall_now_ms": worker_j.anchor_wall_us / 1000.0 + 250.0,
            "latency": [{"source": "s", "hop": "h", "count": 1}]}
    t0 = worker_j.anchor_wall_us / 1000.0
    merged = merge_timelines(local, [(0, dump, t0)], t0_ms=t0)
    assert merged["displayTimeUnit"] == "ms"
    pids = {e["pid"] for e in merged["traceEvents"]}
    assert pids == {0, 1}
    assert merged["otherData"]["workers"] == 1
    assert merged["otherData"]["clock_offsets_ms"][0] != 0.0
    assert merged["otherData"]["latency"][0]["worker"] == 0
    ts = [e["ts"] for e in merged["traceEvents"] if "ts" in e and e["ts"]]
    assert ts == sorted(ts)


# ---------------------------------------------------------------------------
# marker → histogram plumbing
# ---------------------------------------------------------------------------

def test_latency_tracker_records_per_source_hop():
    class FakeClock:
        now = 1_000_000

        def now_ms(self):
            return self.now

        def now_ms_f(self):
            return float(self.now)

    c = FakeClock()
    lt = LatencyTracker(clock_=c)
    marked = (c.now_ms() - 40) / 1000.0          # marked 40ms ago
    m = LatencyMarker(marked, subtask_index=1, source="src")
    lat = lt.record(m, "sink")
    assert lat == pytest.approx(40.0)
    # a skew-negative reading clamps to zero, never a negative sample
    future = LatencyMarker((c.now_ms() + 5000) / 1000.0, source="src")
    assert lt.record(future, "sink") == 0.0
    panel = lt.panel()
    assert len(panel) == 2                       # (src,1,sink) + (src,0,sink)
    row = next(r for r in panel if r["source_subtask"] == 1)
    assert row["source"] == "src" and row["hop"] == "sink"
    assert row["count"] == 1 and row["p99_ms"] == pytest.approx(40.0)
    assert lt.summary() == {"hops": 2, "samples": 2}


def test_latency_tracker_metrics_exported_via_prometheus():
    reg = MetricRegistry()
    group = reg.job_manager_group()
    lt = LatencyTracker().bind_group(group)
    m = LatencyMarker(time.time() - 0.05, source="src")
    for _ in range(4):
        lt.record(m, "agg")
    reporter = PrometheusReporter(registry=reg)
    text = reporter.scrape()
    # summary family with proper quantile labels + _sum/_count and gauges
    assert 'flink_tpu_jobmanager_latency_source_src_0_op_agg' in text
    assert 'quantile="0.99"' in text and 'quantile="0.5"' in text
    assert "_sum " in text and "_count 4" in text
    assert "p99_ms" in text and "p50_ms" in text


def test_latency_tracker_reset_per_execution():
    """reset() drops every hop row (job B must not report job A's hops
    or samples) while a reappearing hop reuses its already-registered
    Histogram, so the panel and the Prometheus exposition keep reading
    ONE reservoir."""
    reg = MetricRegistry()
    lt = LatencyTracker().bind_group(reg.job_manager_group())
    lt.record(LatencyMarker(time.time() - 0.05, source="src"), "agg")
    lt.record(LatencyMarker(time.time() - 0.05, source="src"), "only-a")
    assert {r["hop"] for r in lt.panel()} == {"agg", "only-a"}
    lt.reset()
    assert lt.panel() == []
    assert lt.summary() == {"hops": 0, "samples": 0}
    lt.record(LatencyMarker(time.time() - 0.02, source="src"), "agg")
    panel = lt.panel()
    assert [r["hop"] for r in panel] == ["agg"]
    assert panel[0]["count"] == 1
    # the registered series IS the live reservoir: count restarted at 1,
    # and the job-A-only hop's registered series was cleared, not frozen
    text = PrometheusReporter(registry=reg).scrape()
    assert "latency_source_src_0_op_agg_count 1" in text
    assert "latency_source_src_0_op_only_a_count 0" in text


def test_prometheus_histogram_summary_wire_format():
    """render()-style wire assertion (like the push reporters): a
    Histogram ships as a Prometheus SUMMARY — quantile series, _sum,
    _count — under the sanitized metric name."""
    reg = MetricRegistry()
    h = reg.job_manager_group().histogram("latency.e2e_ms")
    h.update_all(np.arange(1, 101, dtype=np.float64))
    lines = PrometheusReporter(registry=reg).render(reg.all_metrics())
    name = "flink_tpu_jobmanager_latency_e2e_ms"
    assert f"# TYPE {name} summary" in lines
    assert f'{name}{{quantile="0.5"}} 50.5' in lines
    assert f'{name}{{quantile="0.99"}} 99.01' in lines
    assert f"{name}_sum 5050.0" in lines
    assert f"{name}_count 100" in lines


def test_meter_deque_rate_semantics():
    """The O(1)-trim deque keeps get_rate() bit-identical: rate is
    (last - first) count over the retained window."""
    now = [0.0]
    m = Meter(window_s=10.0, clock=lambda: now[0])
    for i in range(5):
        now[0] = float(i)
        m.mark_event(2)
    assert m.get_count() == 10
    assert m.get_rate() == pytest.approx((10 - 2) / 4.0)
    # events beyond the window trim from the LEFT in O(1)
    now[0] = 100.0
    m.mark_event()
    assert m.get_rate() == pytest.approx((11 - 10) / (100.0 - 4.0))


# ---------------------------------------------------------------------------
# MiniCluster end-to-end: config key → markers → histograms → REST
# ---------------------------------------------------------------------------

def test_latency_interval_config_key_wired():
    from flink_tpu.cluster.minicluster import MiniCluster

    config = Configuration().set(MetricOptions.LATENCY_INTERVAL, "5 ms")
    mc = MiniCluster(config=config)
    assert mc.latency_interval_ms == 5
    # explicit arg wins over config
    mc2 = MiniCluster(config=config, latency_interval_ms=11)
    assert mc2.latency_interval_ms == 11
    # tracing config key installs the journal
    config2 = Configuration().set(MetricOptions.TRACING_ENABLED, True) \
        .set(MetricOptions.TRACING_BUFFER, 128)
    mc3 = MiniCluster(config=config2)
    assert mc3.tracing_enabled and tracing.active().capacity == 128


def test_minicluster_latency_and_trace_end_to_end():
    """ONE run: p99 per (source, sink-hop) visible in job_status(), the
    Prometheus exposition, and the REST panel; the span journal holds
    checkpoint lifecycle spans exported as Chrome trace JSON."""
    from flink_tpu.cluster.minicluster import MiniCluster
    from flink_tpu.datastream.api import StreamExecutionEnvironment
    from flink_tpu.rest.server import JobRegistry, RestServer
    from flink_tpu.runtime.checkpoint.storage import InMemoryCheckpointStorage

    env = StreamExecutionEnvironment()
    env.set_parallelism(2)
    n = 120_000
    (env.from_collection(columns={"k": np.arange(n) % 13,
                                  "v": np.ones(n)}, batch_size=128)
        .key_by("k").sum("v").collect())
    plan = env.get_stream_graph("lat-job").to_plan()
    mc = MiniCluster(checkpoint_storage=InMemoryCheckpointStorage(retain=3),
                     checkpoint_interval_ms=20,
                     latency_interval_ms=2, tracing_enabled=True)
    registry = JobRegistry()
    job_id = registry.register("lat-job", mc)
    server = RestServer(registry).start()
    try:
        res = mc.execute(plan, timeout_s=120)
        assert res.state == "FINISHED"
        assert res.completed_checkpoints, "no checkpoint completed"

        # 1. job_status(): per-(source, hop) latency incl. the sink hop
        status = mc.job_status()
        hops = status["latency"]
        assert hops, "no latency hops recorded"
        sink_uids = [v["id"] for v in status["vertices"]
                     if "sink" in v["name"] or "collect" in v["name"]]
        hop_ids = {h["hop"] for h in hops}
        assert any(u in hop_ids for u in sink_uids) or len(hop_ids) >= 2
        assert all(h["p99_ms"] >= 0 and h["count"] > 0 for h in hops)
        # trace summary rides job_status too
        assert status["trace"]["enabled"]
        assert status["trace"]["spans"] > 0
        assert status["trace"]["categories"].get("checkpoint", 0) > 0

        # 2. Prometheus exposition, same run
        text = PrometheusReporter(registry=mc.metrics_registry).scrape()
        assert "latency_source_" in text and 'quantile="0.99"' in text

        # 3. REST: latency JSON + panel + Chrome trace, same run
        with urllib.request.urlopen(
                f"{server.url}/jobs/{job_id}/latency", timeout=10) as r:
            lat = json.loads(r.read())
        assert lat["hops"] and lat["hops"][0]["count"] > 0
        with urllib.request.urlopen(
                f"{server.url}/jobs/{job_id}/latency.html", timeout=10) as r:
            html = r.read().decode()
        assert 'class="lat-row"' in html and "p99 ms" in html
        with urllib.request.urlopen(
                f"{server.url}/jobs/{job_id}/trace", timeout=10) as r:
            trace = json.loads(r.read())
        evs = trace["traceEvents"]
        assert evs and trace["displayTimeUnit"] == "ms"
        cats = {e.get("cat") for e in evs}
        assert "checkpoint" in cats
        names = {e["name"] for e in evs}
        # full lifecycle: trigger → barrier/snapshot → ack → complete
        assert {"checkpoint.trigger", "checkpoint.snapshot",
                "checkpoint.ack", "checkpoint"} <= names
        assert trace["otherData"]["latency"]
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# ProcessCluster: ONE merged timeline across workers
# ---------------------------------------------------------------------------

TRACE_JOB = textwrap.dedent('''
    """Deterministic keyed-sum job, sized so checkpoints land mid-run."""
    import numpy as np
    from flink_tpu.datastream.api import StreamExecutionEnvironment

    N = 60_000
    K = 13

    def build():
        env = StreamExecutionEnvironment()
        env.set_parallelism(2)
        keys = (np.arange(N) % K).astype(np.int64)
        (env.from_collection(columns={"k": keys, "v": np.ones(N)},
                             batch_size=64)
            .key_by("k").sum("v").collect())
        return env.get_stream_graph("trace-job")
''')


def test_process_cluster_latency_without_tracing(tmp_path):
    """``metrics.latency.interval`` alone (no tracing) must still surface
    the per-hop histograms: the workers answer trace_request with an
    empty journal but a full latency panel, and run()'s result carries
    ``latency`` without a ``trace``."""
    from flink_tpu.cluster.distributed import ProcessCluster

    mod = tmp_path / "latonly_job_mod.py"
    mod.write_text(TRACE_JOB)
    sys.path.insert(0, str(tmp_path))
    try:
        pc = ProcessCluster("latonly_job_mod:build", n_workers=1,
                            extra_sys_path=(str(tmp_path),),
                            tracing=False, latency_interval_ms=5)
        res = pc.run(timeout_s=300)
    finally:
        sys.path.remove(str(tmp_path))
        sys.modules.pop("latonly_job_mod", None)
    assert res["state"] == "FINISHED", res["error"]
    assert "trace" not in res
    assert res.get("latency"), "latency panel lost without tracing"
    row = res["latency"][0]
    assert {"hop", "p99_ms", "worker"} <= set(row)


def test_collect_trace_does_not_stall_on_dead_workers():
    """A worker whose control connection EOF'd (SIGKILL, crash) can never
    answer a trace_request — collect_trace must exclude already-dead
    conns up front and shrink its wait when one dies MID-collect, instead
    of sitting out the full timeout."""
    from flink_tpu.cluster.distributed import ProcessCluster

    pc = ProcessCluster("fake_mod:build", n_workers=2)
    sent = []
    pc._to_worker = lambda idx, msg: sent.append(idx)

    # both conns already dead: returns immediately, requests nothing
    pc._conns = {0: object(), 1: object()}
    pc._dead_conn_idx = {0, 1}
    t0 = time.monotonic()
    merged = pc.collect_trace(timeout_s=10.0)
    assert time.monotonic() - t0 < 2.0
    assert sent == [] and merged["otherData"]["requested_workers"] == 0

    # one live conn dying mid-collect unblocks the wait early
    pc._dead_conn_idx = {1}

    def _die_later():
        time.sleep(0.3)
        pc._dead_conn_idx.add(0)
        with pc._trace_cv:
            pc._trace_cv.notify_all()

    threading.Thread(target=_die_later, daemon=True).start()
    t0 = time.monotonic()
    merged = pc.collect_trace(timeout_s=10.0)
    assert time.monotonic() - t0 < 5.0, "stalled on a dead worker"
    assert sent == [0] and merged["otherData"]["workers"] == 0


def test_process_cluster_merged_timeline(tmp_path):
    """A ProcessCluster job with tracing on yields ONE merged Chrome
    timeline: coordinator checkpoint spans (pid 0) + both workers' task
    spans, clock-offset aligned, plus the workers' latency panels."""
    from flink_tpu.cluster.distributed import ProcessCluster

    mod = tmp_path / "trace_job_mod.py"
    mod.write_text(TRACE_JOB)
    sys.path.insert(0, str(tmp_path))
    try:
        pc = ProcessCluster("trace_job_mod:build", n_workers=2,
                            checkpoint_interval_ms=50,
                            extra_sys_path=(str(tmp_path),),
                            tracing=True, latency_interval_ms=5)
        res = pc.run(timeout_s=300)
    finally:
        sys.path.remove(str(tmp_path))
        sys.modules.pop("trace_job_mod", None)
    assert res["state"] == "FINISHED", res["error"]
    trace = res["trace"]
    assert trace is pc.last_trace
    other = trace["otherData"]
    assert other["requested_workers"] == 2
    assert other["workers"] == 2, "a worker's ring never arrived"
    assert set(other["clock_offsets_ms"]) == {0, 1}
    evs = trace["traceEvents"]
    pids = {e["pid"] for e in evs}
    assert {0, 1, 2} <= pids, f"merged timeline missing processes: {pids}"
    # coordinator lifecycle + worker snapshot spans on the SAME timeline
    names_by_pid = {}
    for e in evs:
        names_by_pid.setdefault(e["pid"], set()).add(e["name"])
    assert "checkpoint.trigger" in names_by_pid[0]
    worker_names = names_by_pid.get(1, set()) | names_by_pid.get(2, set())
    assert "checkpoint.snapshot" in worker_names
    # workers recorded marker latency at their hops
    assert other["latency"], "no worker latency panels in the merge"
    assert {"worker", "hop", "p99_ms"} <= set(other["latency"][0])
    # one ordered timeline (metadata events carry no ts)
    ts = [e["ts"] for e in evs if "ts" in e]
    assert ts == sorted(ts)
    json.dumps(trace)                    # Perfetto-loadable = valid JSON


# ---------------------------------------------------------------------------
# the span layer on the profiler's clock (ISSUE-26): one emit path, two
# sinks — the journal when installed, jax.profiler's TraceMe always
# ---------------------------------------------------------------------------

class _GatedSource:
    """Two splits of a keyed stream that park half way — yielding a
    watermark their timestamps operator swallows, so the source task keeps
    serving its command queue — until the test releases them: the cut is
    taken while both splits are live, whatever the machine's speed."""

    bounded = True

    def __init__(self, n=24_000, batch=512):
        from flink_tpu.connectors.sources import CollectionSource

        ts = np.arange(n, dtype=np.int64) // (n // 1000)      # 0..999 ms
        self._rows = CollectionSource(
            columns={"k": (np.arange(n) * 7) % 257,
                     "v": np.ones(n, np.float32), "ts": ts},
            timestamp_column="ts", batch_size=batch)
        self.release = threading.Event()

    def create_splits(self, parallelism):
        from flink_tpu.connectors.sources import SourceSplit

        return [SourceSplit(self, i, parallelism) for i in range(parallelism)]

    def read_split(self, index, of):
        from flink_tpu.core.batch import LONG_MIN, Watermark

        batches = list(self._rows.read_split(index, of))
        yield from batches[:len(batches) // 2]
        while not self.release.is_set():
            time.sleep(0.001)
            yield Watermark(LONG_MIN)
        yield from batches[len(batches) // 2:]


def _window_ops(cluster):
    from flink_tpu.operators.window_agg import WindowAggOperator

    return [m for t in cluster.tasks()
            for m in getattr(t.operator, "operators", [t.operator])
            if isinstance(m, WindowAggOperator)]


def _run_keyed_window_job(channel_capacity=4096):
    """Parallelism 2, device tier, 250 ms tumbling windows over one second
    of event time, ONE checkpoint taken while both splits are parked (so
    every window task aligns two live channels) and four fires a subtask.
    Returns (cluster, result)."""
    import jax.numpy as jnp

    from flink_tpu.core.functions import SumAggregator
    from flink_tpu.datastream.api import StreamExecutionEnvironment
    from flink_tpu.runtime.checkpoint.storage import InMemoryCheckpointStorage
    from flink_tpu.windowing.assigners import TumblingEventTimeWindows

    env = StreamExecutionEnvironment(parallelism=2)
    assert env.last_cluster is None
    source = _GatedSource()
    (env.from_source(source, name="gated")
        .assign_timestamps_and_watermarks(0, timestamp_column="ts")
        .key_by("k").window(TumblingEventTimeWindows.of(250))
        .aggregate(SumAggregator(jnp.float32), value_column="v",
                   emit_tier="device")
        .collect())

    def cut():
        try:
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                cluster = env.last_cluster
                ops = _window_ops(cluster) if cluster is not None else []
                if len(ops) == 2 and all(
                        op.phase_ns.get("device_dispatch") for op in ops):
                    break
                time.sleep(0.002)
            cid = None
            while cid is None and time.monotonic() < deadline:
                cid = cluster.trigger_checkpoint()
                time.sleep(0.002)
            while time.monotonic() < deadline and cid not in \
                    cluster.job_status()["completed_checkpoints"]:
                time.sleep(0.002)
        finally:
            source.release.set()

    driver = threading.Thread(target=cut, daemon=True)
    driver.start()
    result = env.execute_cluster(
        "span-job", storage=InMemoryCheckpointStorage(),
        checkpoint_interval_ms=0, channel_capacity=channel_capacity,
        timeout_s=120.0)
    driver.join(timeout=60.0)
    assert not driver.is_alive()
    assert result.state == "FINISHED", result.error
    assert list(result.completed_checkpoints) == [1]
    return env.last_cluster, result


def _run_sql_window_job():
    """The statement of BASELINE config 5 at a small size, parallelism 1:
    four 250 ms TUMBLE windows through the planner's `sql-pre-project` and
    `sql-project` maps.  Returns the cluster."""
    from flink_tpu.datastream.api import StreamExecutionEnvironment
    from flink_tpu.sql.table_env import TableEnvironment

    n = 512
    tenv = TableEnvironment(parallelism=1)
    tenv.register_collection(
        "lineitem", columns={"k": np.arange(n, dtype=np.int64) % 64,
                             "v": np.ones(n, np.float32),
                             "ts": np.arange(n, dtype=np.int64) * 1000 // n},
        rowtime="ts", batch_size=64)
    env = StreamExecutionEnvironment(parallelism=1)
    (tenv.sql_query(
        "SELECT k, TUMBLE_END(ts, INTERVAL '0.25' SECOND) AS window_end, "
        "SUM(v) AS total, COUNT(*) AS n, AVG(v) AS mean FROM lineitem "
        "GROUP BY k, TUMBLE(ts, INTERVAL '0.25' SECOND)")
        .to_data_stream(env).collect())
    result = env.execute_cluster("sql-span-job", timeout_s=120.0)
    assert result.state == "FINISHED", result.error
    return env.last_cluster


#: span -> (enclosing span on the same thread or None, identifier argument
#: shared along one cut / one fire or None): the table of
#: docs/operations.md "Tracing and latency tracking"
SPAN_TABLE = {
    "source.next": (None, None),
    "exchange.partition": (None, None),
    "task.input_wait": (None, None),
    "task.process_batch": (None, None),
    # one span per chained operator: `chain.<name>` opened by the chain,
    # or the member's own (`window_agg.process_batch`, `sink.invoke`)
    "chain.timestamps": ("task.process_batch", None),
    "chain.key-by": ("task.process_batch", None),
    "window_agg.process_batch": ("task.process_batch", None),
    "window_agg.probe": ("window_agg.process_batch", None),
    "window_agg.stage": ("window_agg.process_batch", None),
    "window_agg.device_step": ("window_agg.process_batch", None),
    # the dispatch in its parts: the hand-off and the thunk on the lane
    # thread (on a mesh the routing before the launch: `_mesh_batches`),
    # the way back on the thread that waited
    "device.handoff_wait": (None, None),
    "window_agg.exchange_route": (None, None),
    "window_agg.launch": (None, None),
    "device.return_wait": ("window_agg.device_step", None),
    "window_agg.mirror": (None, None),          # host tier, below
    "window_agg.probe_mirror": (None, None),    # host tier, native mirror
    "window_agg.fire": (None, "window_end"),
    "window_agg.fire_dispatch": ("window_agg.fire", "window_end"),
    "window_agg.fire_d2h": ("window_agg.fire", "window_end"),
    "window_agg.fire_assemble": ("window_agg.fire", "window_end"),
    "checkpoint.align": (None, "checkpoint"),
    "checkpoint.snapshot": (None, "checkpoint"),
    "window_agg.snapshot": ("checkpoint.snapshot", "checkpoint"),
    "window_agg.snapshot_d2h": ("window_agg.snapshot", "checkpoint"),
    "window_agg.snapshot_assemble": ("window_agg.snapshot", "checkpoint"),
    # on the thread of whichever task acknowledged last
    "checkpoint.complete": (None, "checkpoint"),
    "checkpoint.store": ("checkpoint.complete", "checkpoint"),
    "sink.invoke": (None, "window_end"),
    # a SQL job only (`_run_sql_window_job`): the planner's two maps
    "sql.pre_project": (None, None),
    "sql.project": (None, "window_end"),
}


def _host_tier_batches(native):
    """Two batches through a host-tier operator on the calling thread: the
    `mirror` phase (numpy mirror) or `probe_mirror` (native mirror)."""
    import jax.numpy as jnp

    from flink_tpu.core.batch import RecordBatch
    from flink_tpu.core.functions import RuntimeContext, SumAggregator
    from flink_tpu.operators.window_agg import WindowAggOperator
    from flink_tpu.windowing.assigners import TumblingEventTimeWindows

    op = WindowAggOperator(TumblingEventTimeWindows.of(250),
                           SumAggregator(jnp.float32), key_column="k",
                           value_column="v", emit_tier="host",
                           native_emit=native)
    op.open(RuntimeContext())
    for i in range(2):
        op.process_batch(RecordBatch(
            {"k": np.arange(64, dtype=np.int64), "v": np.ones(64, np.float32)},
            timestamps=np.full(64, 10 * i, np.int64)))
    op.close()
    return set(op.phase_ns)


def _device_tier_op(mesh_devices=None):
    """A device-tier operator on the calling thread: the one-chip one, or
    the mesh one over ``mesh_devices`` of the forced host devices."""
    import jax.numpy as jnp

    from flink_tpu.core.functions import RuntimeContext, SumAggregator
    from flink_tpu.operators.window_agg import WindowAggOperator
    from flink_tpu.windowing.assigners import TumblingEventTimeWindows

    kw = dict(agg=SumAggregator(jnp.float32), key_column="k",
              value_column="v", emit_tier="device")
    assigner = TumblingEventTimeWindows.of(250)
    if mesh_devices is None:
        op = WindowAggOperator(assigner, **kw)
    else:
        from flink_tpu.parallel.mesh import make_mesh
        from flink_tpu.parallel.mesh_runtime import MeshWindowAggOperator

        op = MeshWindowAggOperator(assigner, mesh=make_mesh(mesh_devices),
                                   **kw)
    op.open(RuntimeContext())
    return op


def _fold_batches(op, n=4):
    from flink_tpu.core.batch import RecordBatch

    for i in range(n):
        op.process_batch(RecordBatch(
            {"k": np.arange(256, dtype=np.int64),
             "v": np.ones(256, np.float32)},
            timestamps=np.full(256, 10 * i, np.int64)))


def _mesh_batches():
    """Four batches through a four-device mesh operator on the calling
    thread: `exchange_route` and `launch` on its dispatch lane."""
    op = _device_tier_op(4)
    _fold_batches(op)
    op.close()
    return set(op.phase_ns)


@pytest.fixture(scope="module")
def profiled_job(tmp_path_factory):
    """The job under a profiler session somebody else might have started
    (here: the test): per host thread line of the `.xplane.pb`, the
    program's spans as (name, start ns, end ns, stats)."""
    import glob

    import jax
    from jax.profiler import ProfileData

    tracing.uninstall()
    out = str(tmp_path_factory.mktemp("xplane"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=options)
    try:
        _run_keyed_window_job()
        phases = (_host_tier_batches(False) | _host_tier_batches(True)
                  | _mesh_batches())
        sql_cluster = _run_sql_window_job()
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(f"{out}/plugins/profile/*/*.xplane.pb")
    assert len(found) == 1
    threads = []
    for plane in ProfileData.from_file(found[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [(ev.name, int(ev.start_ns),
                      int(ev.start_ns + ev.duration_ns), dict(ev.stats))
                     for ev in line.events if ev.name in SPAN_TABLE
                     or ev.name == "exchange.put_wait"]
            if spans:
                threads.append(spans)
    # the SQL job's threads apart: the keyed job's tests count its own
    sql_threads = [spans for spans in threads
                   if any(s[0].startswith("sql.") for s in spans)]
    return {"threads": [t for t in threads if t not in sql_threads],
            "sql_threads": sql_threads, "sql_cluster": sql_cluster,
            "host_tier_phases": phases}


def _enclosing(spans, child, parent_name):
    return [p for p in spans if p[0] == parent_name
            and p[1] <= child[1] and child[2] <= p[2]]


@pytest.mark.parametrize("name", sorted(SPAN_TABLE))
def test_span_lands_in_the_profilers_trace(profiled_job, name):
    parent, ident = SPAN_TABLE[name]
    if name == "window_agg.probe_mirror" \
            and "probe_mirror" not in profiled_job["host_tier_phases"]:
        pytest.skip("the native mirror did not build here")
    hits = [(spans, s) for spans in
            profiled_job["threads"] + profiled_job["sql_threads"]
            for s in spans if s[0] == name]
    assert hits, f"no {name!r} event on any host line"
    for spans, s in hits:
        assert s[2] >= s[1]
        if parent is None or not any(t[0] == "task.process_batch"
                                     for t in spans):
            continue        # no parent, or the host-tier run's own thread
        if ident == "checkpoint" and ident not in s[3]:
            continue        # the task's final snapshot: no cut caused it
        inside = _enclosing(spans, s, parent)
        assert inside, f"{name} at {s[1]} is outside every {parent}"
        if ident is not None:
            assert inside[0][3][ident] == s[3][ident]
    if ident is not None:
        assert any(ident in s[3] for _, s in hits)


def test_one_cut_and_one_fire_share_their_identifier(profiled_job):
    """`checkpoint=` is equal on everything a cut causes on a window task's
    thread and `window_end=` on everything a fire causes, through to the
    sink; with channels this deep no put ever blocked, so no
    `exchange.put_wait` exists."""
    coordinator = {"checkpoint.complete", "checkpoint.store"}
    cut_names = {n for n, (_, ident) in SPAN_TABLE.items()
                 if ident == "checkpoint"} - coordinator
    for name in coordinator:        # once a cut, on the last acker's thread
        assert [s[3]["checkpoint"] for spans in profiled_job["threads"]
                for s in spans if s[0] == name] == [1]
    fire_names = {n for n, (_, ident) in SPAN_TABLE.items()
                  if ident == "window_end"} - {"sql.project"}
    window_threads = [spans for spans in profiled_job["threads"]
                      if any(s[0] == "window_agg.fire" for s in spans)]
    assert len(window_threads) == 2
    for spans in window_threads:
        cut = {s[0] for s in spans if s[3].get("checkpoint") == 1}
        assert cut_names <= cut, cut_names - cut
        ends = sorted({s[3]["window_end"] for s in spans
                       if s[0] == "window_agg.fire"})
        assert ends == [250, 500, 750, 1000]
        for end in ends:
            fire = [s for s in spans if s[3].get("window_end") == end]
            assert {s[0] for s in fire} == fire_names
            # the rows reach the sink after the fire that produced them
            sink = [s for s in fire if s[0] == "sink.invoke"]
            whole = [s for s in fire if s[0] == "window_agg.fire"]
            assert sink[0][1] >= whole[0][2]
            assert sink[0][3]["records"] > 0
    assert not any(s[0] == "exchange.put_wait"
                   for spans in profiled_job["threads"] for s in spans)


def test_a_sql_jobs_projections_are_spans_with_counters_beside_them(
        profiled_job):
    """`sql.pre_project` on the source task's thread, one a batch;
    `sql.project` on the window task's, one a fired batch, after the fire
    that produced its rows and before the sink that takes them, all three
    under one `window_end`; `job_status()` counts the same batches and
    rows."""
    by_name = {name: [s for spans in profiled_job["sql_threads"]
                      for s in spans if s[0] == name]
               for name in ("sql.pre_project", "sql.project")}
    assert [s[3]["records"] for s in by_name["sql.pre_project"]] == [64] * 8
    assert not any("window_end" in s[3] for s in by_name["sql.pre_project"])
    assert [s[3]["window_end"] for s in by_name["sql.project"]] \
        == [250, 500, 750, 1000]
    window_thread, = [spans for spans in profiled_job["sql_threads"]
                      if any(s[0] == "sql.project" for s in spans)]
    for project in by_name["sql.project"]:
        end = project[3]["window_end"]
        fire, = [s for s in window_thread if s[0] == "window_agg.fire"
                 and s[3]["window_end"] == end]
        sink, = [s for s in window_thread if s[0] == "sink.invoke"
                 and s[3].get("window_end") == end]
        assert fire[2] <= project[1] <= project[2] <= sink[1]
        assert project[3]["records"] == sink[3]["records"] == 64
    counted = {}
    for vertex in profiled_job["sql_cluster"].job_status()["vertices"]:
        for subtask in vertex["subtasks"]:
            counted.update(subtask["chain_stats"])
    assert {k: (v["batches"], v["rows"]) for k, v in counted.items()
            if k.startswith("sql.")} == {
        "sql.pre_project": (8, 512), "sql.project": (4, 256)}
    for name, spans in by_name.items():
        # the counter's clock starts before the span and stops after it
        assert counted[name]["ns"] >= sum(s[2] - s[1] for s in spans) > 0


def test_put_wait_is_a_span_only_when_a_put_blocks():
    """Channels of capacity 1 in front of window tasks that compile their
    first steps: the sources block, and only then does the span exist."""
    j = tracing.install(SpanJournal(1 << 15))
    cluster, _ = _run_keyed_window_job(channel_capacity=1)
    waits = [s for s in j.spans() if s[3] == "exchange.put_wait"]
    blocked_ns = sum(ch.backpressured_ns for t in cluster.tasks()
                     for out in t.outputs for ch in out.channels)
    assert waits and all(s[4] == "exchange" for s in waits)
    assert 0 < sum(s[2] for s in waits) <= blocked_ns


def test_no_session_no_journal_costs_no_journal_entry(monkeypatch):
    """With neither sink open the emit path builds no journal entry (no
    `_SpanCtx`, no `record`), and the operator's `phase_ns` holds the new
    phases under their parents: a fire is at least its dispatch, its wait
    for the device and its row assembly, a snapshot at least its reads and
    its assembly."""
    def refuse(*_a, **_k):
        raise AssertionError("a journal entry was built with no journal")

    monkeypatch.setattr(tracing._SpanCtx, "__init__", refuse)
    monkeypatch.setattr(SpanJournal, "record", refuse)
    assert tracing.active() is None
    cluster, _ = _run_keyed_window_job()
    ops = _window_ops(cluster)
    assert len(ops) == 2
    for op in ops:
        ns = op.phase_ns
        for key in ("probe", "stage", "device_dispatch", "fire",
                    "fire_dispatch", "fire_d2h", "fire_assemble", "snapshot",
                    "snapshot_d2h", "snapshot_assemble"):
            assert ns.get(key, 0) > 0, key
        assert ns["fire"] >= (ns["fire_dispatch"] + ns["fire_d2h"]
                              + ns["fire_assemble"])
        assert ns["snapshot"] >= ns["snapshot_d2h"] + ns["snapshot_assemble"]
        assert 0 < op.phase_bytes["d2h_fire"] < op.phase_bytes["d2h"]
        assert 0 < op.phase_bytes["d2h_snapshot"] < op.phase_bytes["d2h"]
        assert op.phase_bytes["d2h"] == (op.phase_bytes["d2h_fire"]
                                         + op.phase_bytes["d2h_snapshot"])


#: journal category of every span of the cells' path (docs/operations.md)
SPAN_CATEGORIES = {
    "source.next": "source", "exchange.partition": "exchange",
    "task.input_wait": "task", "task.process_batch": "task",
    "window_agg.probe": "hot_stage", "window_agg.stage": "hot_stage",
    "window_agg.device_step": "hot_stage", "window_agg.fire": "hot_stage",
    "window_agg.fire_dispatch": "hot_stage",
    "window_agg.fire_d2h": "hot_stage",
    "window_agg.fire_assemble": "hot_stage",
    "window_agg.snapshot": "hot_stage",
    "window_agg.snapshot_d2h": "hot_stage",
    "window_agg.snapshot_assemble": "hot_stage",
    "checkpoint.align": "checkpoint", "checkpoint.snapshot": "checkpoint",
    "checkpoint.alignment": "checkpoint", "checkpoint.barrier": "checkpoint",
    "checkpoint.trigger": "checkpoint", "checkpoint.ack": "checkpoint",
    "checkpoint": "checkpoint", "checkpoint.complete": "checkpoint",
    "checkpoint.store": "checkpoint", "sink.invoke": "sink",
    "chain.timestamps": "chain", "chain.key-by": "chain",
    "window_agg.process_batch": "hot_stage",
    "window_agg.launch": "hot_stage",
    "device.handoff_wait": "device_health",
    "device.return_wait": "device_health",
}


def test_journal_gets_the_same_names_without_a_session():
    """A journal installed, no profiler session: the names of the
    profiler's spans arrive in the journal, each under its category, with
    the same identifiers."""
    j = tracing.install(SpanJournal(1 << 15))
    _run_keyed_window_job()
    spans = j.spans()
    assert j.dropped == 0
    seen = {}
    for _ph, _ts, _dur, name, cat, _tid, _args in spans:
        seen.setdefault(name, set()).add(cat)
    for name, cat in SPAN_CATEGORIES.items():
        assert seen.get(name) == {cat}, (name, seen.get(name))
    assert "exchange.put_wait" not in seen
    fires = [s for s in spans if s[3] == "window_agg.fire_d2h"]
    assert sorted({s[6]["window_end"] for s in fires}) == [250, 500, 750, 1000]
    cut = [s for s in spans if s[3] == "window_agg.snapshot_d2h" and s[6]]
    assert [s[6] for s in cut] == [{"checkpoint": 1}] * 2


def test_cluster_exposes_its_running_tasks():
    """`env.last_cluster.tasks()` is the public way to the job's subtasks
    (the harness and `python -m flink_tpu` read it): a copy of the
    deployment's list, sources first."""
    from flink_tpu.cluster.task import SourceSubtask, Subtask

    cluster, _ = _run_keyed_window_job()
    tasks = cluster.tasks()
    assert [type(t) for t in tasks] == [SourceSubtask] * 2 + [Subtask] * 2
    assert tasks is not cluster.tasks() and tasks == cluster.tasks()
    assert sum(t.records_in for t in tasks[2:]) == 24_000


# ---------------------------------------------------------------------------
# one account of a thread's time (ISSUE-37): CPU beside wall in every
# phase, the dispatch in its parts, one counter set per chained operator
# ---------------------------------------------------------------------------

#: what a guarded update is made of, inside `device_dispatch`
DISPATCH_PARTS = ("dispatch_handoff", "exchange_route", "launch",
                  "dispatch_return")


@pytest.fixture(scope="module")
def keyed_job():
    """The keyed window job once, no journal, no session: (cluster, the
    wall ns its tasks' loops can have taken at most)."""
    tracing.uninstall()
    t0 = time.monotonic_ns()
    cluster, _ = _run_keyed_window_job()
    return cluster, time.monotonic_ns() - t0


def test_every_phase_keeps_its_cpu_time_beside_its_wall_time(keyed_job):
    """`phase_ns["<phase>_cpu"]` is the thread's CPU time inside the
    phase: never above the wall time, above zero where the phase is numpy
    work; the two hand-offs are waits and keep no CPU time."""
    for op in _window_ops(keyed_job[0]):
        ns = op.phase_ns
        phases = [k for k in ns if not k.endswith("_cpu")]
        assert {"process_batch", "probe", "stage", "device_dispatch",
                "launch", "fire", "snapshot"} <= set(phases)
        for key in phases:
            if key in ("dispatch_handoff", "dispatch_return"):
                assert key + "_cpu" not in ns
                continue
            assert 0 <= ns[key + "_cpu"] <= ns[key], key
        assert ns["stage_cpu"] > 0 and ns["probe_cpu"] > 0
        # the operator's whole entry holds the phases of its batches
        assert ns["process_batch"] >= (ns["probe"] + ns["stage"]
                                       + ns["device_dispatch"])


def test_a_phase_that_sleeps_shows_wall_far_above_cpu():
    acc = tracing.TimeAccount()
    with tracing.PhaseTimer(acc, "wait", "test.wait"):
        time.sleep(0.05)
    with tracing.PhaseTimer(acc, "spin", "test.spin"):
        end = time.perf_counter() + 0.05
        while time.perf_counter() < end:
            pass
    assert acc["wait"] >= 50_000_000 and acc["wait_cpu"] < 5_000_000
    assert acc["spin_cpu"] <= acc["spin"]
    # a busy loop is on the CPU unless the machine took it away
    assert acc["spin_cpu"] > acc["spin"] // 4


def test_the_cpu_clock_is_read_once_an_interval_and_a_key(monkeypatch):
    """A reading of the thread's CPU clock is a system call, so a phase
    gets one per `CPU_READ_EVERY_NS`; an entry in between takes the CPU
    share the last reading found, of its own wall time.  With the
    interval at zero every entry is read."""
    reads = []
    real = time.thread_time_ns

    def counted():
        reads.append(1)
        return real()

    monkeypatch.setattr(tracing.time, "thread_time_ns", counted)

    def spin_then_sleep(acc):
        with tracing.PhaseTimer(acc, "x", None):
            end = time.perf_counter() + 0.02
            while time.perf_counter() < end:
                pass
        first = dict(acc)
        with tracing.PhaseTimer(acc, "x", None):
            time.sleep(0.02)
        return first, {k: acc[k] - first[k] for k in first}

    monkeypatch.setattr(tracing, "CPU_READ_EVERY_NS", 10**12)
    first, second = spin_then_sleep(tracing.TimeAccount())
    assert len(reads) == 2                   # the first entry alone
    share = first["x_cpu"] / first["x"]
    assert second["x_cpu"] == pytest.approx(share * second["x"], rel=1e-3)
    assert second["x_cpu"] <= second["x"]
    del reads[:]
    monkeypatch.setattr(tracing, "CPU_READ_EVERY_NS", 0)
    first, second = spin_then_sleep(tracing.TimeAccount())
    assert len(reads) == 4
    assert second["x_cpu"] < 5_000_000 <= 20_000_000 <= second["x"]


@pytest.mark.parametrize("mesh_devices", [None, 4],
                         ids=["one-chip", "mesh-4"])
def test_a_dispatch_is_accounted_in_its_parts(mesh_devices):
    """`device_dispatch` >= hand-off + (`exchange_route` on a mesh) +
    `launch` + the way back; the thunk's phases are spans on the lane
    thread, the way back a span on the thread that waited."""
    j = tracing.install(SpanJournal(1 << 12))
    op = _device_tier_op(mesh_devices)
    _fold_batches(op)
    ns = dict(op.phase_ns)
    dispatches = op.fused_stats()["hot_dispatches"]
    op.close()
    assert dispatches == 4
    parts = [p for p in DISPATCH_PARTS
             if mesh_devices is not None or p != "exchange_route"]
    assert all(ns[p] > 0 for p in parts), ns
    assert ("exchange_route" in ns) == (mesh_devices is not None)
    assert ns["device_dispatch"] >= sum(ns[p] for p in parts)
    assert ns["launch_cpu"] <= ns["launch"]
    me = threading.current_thread().name
    by_name = {}
    for _ph, _ts, dur, name, _cat, tid, _args in j.spans():
        by_name.setdefault(name, []).append((tid, dur))
    lane_spans = ["device.handoff_wait", "window_agg.launch"] + (
        ["window_agg.exchange_route"] if mesh_devices is not None else [])
    for name in lane_spans:
        assert len(by_name[name]) == dispatches, name
        assert {tid for tid, _ in by_name[name]} != {me}
        assert all(tid.startswith("device-lane") for tid, _ in by_name[name])
    assert [tid for tid, _ in by_name["device.return_wait"]] \
        == [me] * dispatches
    # the spans that cross threads carry what the counters hold
    assert sum(d for _, d in by_name["device.handoff_wait"]) \
        == ns["dispatch_handoff"]
    assert sum(d for _, d in by_name["device.return_wait"]) \
        == ns["dispatch_return"]


def _chain_stats(cluster):
    """[(task, its chain_stats)] of the cluster's tasks."""
    return [(t, t.chain_stats) for t in cluster.tasks()]


def test_a_chain_keeps_one_counter_set_per_member(keyed_job):
    """key-by -> window -> sink on the window tasks, the source's own
    chain on the source tasks: a span name and {batches, rows, ns, cpu_ns}
    per member, rows = records in, the sum of ns inside `busy_ns`;
    `job_status()` shows the same per subtask."""
    cluster = keyed_job[0]
    source_chain = ["chain.gated", "chain.timestamps"]
    window_chain = ["chain.key-by", "window_agg.process_batch",
                    "sink.invoke"]
    for task, stats in _chain_stats(cluster):
        assert list(stats) in (source_chain, window_chain)
        first = stats[list(stats)[0]]
        assert first["rows"] == task.records_in > 0
        for span, counted in stats.items():
            assert set(counted) == {"batches", "rows", "ns", "cpu_ns"}
            assert counted["batches"] > 0 and counted["rows"] > 0
            assert 0 < counted["cpu_ns"] <= counted["ns"], span
        assert sum(c["ns"] for c in stats.values()) <= task.busy_ns
        if list(stats) == window_chain:
            assert stats["window_agg.process_batch"]["rows"] \
                == task.records_in
            assert stats["sink.invoke"]["batches"] == 4      # one a fire
            # the operator times its own entry: the chain reads that
            op, = _window_ops_of(task)
            assert stats["window_agg.process_batch"]["ns"] \
                == op.phase_ns["process_batch"]
    status = [sub["chain_stats"] for v in cluster.job_status()["vertices"]
              for sub in v["subtasks"]]
    assert sorted(map(list, status)) == sorted(
        [source_chain] * 2 + [window_chain] * 2)


def test_job_status_gives_cpu_beside_busy(keyed_job):
    """`cpu_ratio` = the task thread's CPU time over busy + idle +
    backpressure, per subtask and per vertex, beside the three ratios
    that sum to one."""
    cluster, wall_ns = keyed_job
    vertices = cluster.job_status()["vertices"]
    assert len(vertices) == 2
    for vertex in vertices:
        assert 0 < vertex["cpu_ratio"] <= 1.0
        for sub in vertex["subtasks"]:
            assert 0 < sub["cpu_ratio"] <= 1.0
            assert sub["busy_ratio"] + sub["idle_ratio"] \
                + sub["backpressure_ratio"] == pytest.approx(1.0)
            task_thread, = [n for n in sub["thread_cpu_ns"]
                            if n.startswith("task-")]
            assert 0 < sub["thread_cpu_ns"][task_thread] <= wall_ns
    source, window = vertices
    for sub in source["subtasks"]:
        # channels this deep never filled: the sources were busy throughout
        assert sub["backpressure_ratio"] == 0.0 == sub["idle_ratio"]
    for task in cluster.tasks():
        assert task.busy_ns + task.idle_ns + task.backpressure_ns \
            <= wall_ns


def _window_ops_of(task):
    from flink_tpu.operators.window_agg import WindowAggOperator

    return [m for m in getattr(task.operator, "operators", [task.operator])
            if isinstance(m, WindowAggOperator)]


def test_the_sql_plans_chains_are_counted_member_by_member(profiled_job):
    cluster = profiled_job["sql_cluster"]
    (source, source_stats), (window, window_stats) = _chain_stats(cluster)
    assert list(source_stats) == ["chain.table:lineitem",
                                  "chain.sql-rowtime", "sql.pre_project"]
    assert list(window_stats) == ["chain.key-by", "window_agg.process_batch",
                                  "sql.project", "sink.invoke"]
    for task, stats in ((source, source_stats), (window, window_stats)):
        assert stats[list(stats)[0]]["rows"] == task.records_in == 512
        assert sum(c["ns"] for c in stats.values()) <= task.busy_ns
    # every fired row goes through the projection and then the sink
    assert window_stats["sql.project"]["rows"] \
        == window_stats["sink.invoke"]["rows"] == 256
