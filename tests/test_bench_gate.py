"""bench.py --check regression gate (VERDICT r3 next #2).

Unit-tests the budget comparison itself, and (slow tier) runs the real
smoke bench under --check so a structural perf regression fails the suite
before the driver sees it — the in-repo answer to the r1->r2 0.84M rec/s
surprise."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from bench import check_budget  # noqa: E402


def _result(rps=10e6, p99=10.0, phases=None, vs_numpy=None, elapsed=None):
    r = {"value": rps, "p99_fire_latency_ms": p99,
         "details": {"phases_ms": phases or {"probe_mirror": 100.0}}}
    if vs_numpy is not None:
        r["vs_numpy_baseline"] = vs_numpy
    if elapsed is not None:
        r["details"]["elapsed_ms"] = elapsed
    return r


def _budget(**kw):
    b = {"min_rps": 5e6, "max_p99_ms": 30.0,
         "max_phase_ms": {"probe_mirror": 500.0}}
    b.update(kw)
    return b


def test_check_budget_pass():
    assert check_budget(_result(), _budget()) == []


def test_check_budget_rps_floor():
    viol = check_budget(_result(rps=1e6), _budget())
    assert len(viol) == 1 and "rec/s" in viol[0]


def test_check_budget_p99_ceiling():
    viol = check_budget(_result(p99=45.0), _budget())
    assert len(viol) == 1 and "p99" in viol[0]


def test_check_budget_phase_ceiling():
    viol = check_budget(_result(phases={"probe_mirror": 900.0}), _budget())
    assert len(viol) == 1 and "probe_mirror" in viol[0]


def test_check_budget_unknown_phase_ignored():
    """A budgeted phase absent from the run (e.g. numpy fallback reports
    'probe'+'mirror' instead of 'probe_mirror') is not a violation."""
    b = _budget(max_phase_ms={"probe_mirror": 500.0, "mirror": 400.0})
    assert check_budget(_result(), b) == []


def test_check_budget_vs_numpy_floor():
    """CPU-forced runs must not lose to flat single-core numpy (the
    acceptance floor of the pipelined hot path)."""
    b = _budget(min_vs_numpy=1.0)
    assert check_budget(_result(vs_numpy=2.05), b) == []
    viol = check_budget(_result(vs_numpy=0.6), b)
    assert len(viol) == 1 and "vs_numpy" in viol[0]
    # results without the field (configN runners) are not violations
    assert check_budget(_result(), b) == []


def test_check_budget_probe_mirror_frac():
    b = _budget(max_probe_mirror_frac=0.85, max_phase_ms={})
    ok = _result(phases={"probe_mirror": 700.0}, elapsed=1000.0)
    assert check_budget(ok, b) == []
    viol = check_budget(
        _result(phases={"probe_mirror": 950.0}, elapsed=1000.0), b)
    assert len(viol) == 1 and "probe_mirror" in viol[0]
    # no elapsed / no probe_mirror phase (numpy fallback): not a violation
    assert check_budget(_result(phases={"probe": 950.0},
                                elapsed=1000.0), b) == []
    assert check_budget(_result(phases={"probe_mirror": 950.0}), b) == []


def _mesh_result(rps_pod=4e6, per_shard=(150.0, 120.0), phases=None,
                 ok=True):
    return {"records_per_sec_pod": rps_pod, "ok": ok,
            "details": {"phases_ms": phases or {"probe_mirror": 600.0},
                        "probe_mirror_shard_ms": list(per_shard)}}


def _mesh_budget(**kw):
    b = {"min_rps_pod": 1.5e6, "max_shard_probe_share": 0.85,
         "max_phase_ms": {"probe_mirror": 2000.0}}
    b.update(kw)
    return b


def test_check_mesh_budget_pass():
    from bench import check_mesh_budget
    assert check_mesh_budget(_mesh_result(), _mesh_budget()) == []


def test_check_mesh_budget_pod_floor():
    from bench import check_mesh_budget
    viol = check_mesh_budget(_mesh_result(rps_pod=1e5), _mesh_budget())
    assert len(viol) == 1 and "rec/s/pod" in viol[0]


def test_check_mesh_budget_shard_share_ceiling():
    """A 'sharded' probe whose whole fold sits on one shard is fictional
    sharding — the share ceiling catches it."""
    from bench import check_mesh_budget
    viol = check_mesh_budget(_mesh_result(per_shard=(600.0, 1.0)),
                             _mesh_budget())
    assert len(viol) == 1 and "not\ndecomposed".replace("\n", " ") \
        in viol[0].replace("\n", " ")
    # single-device / serial-probe runs (one live entry) are exempt
    assert check_mesh_budget(_mesh_result(per_shard=(600.0,)),
                             _mesh_budget()) == []
    assert check_mesh_budget(_mesh_result(per_shard=(600.0, 0.0)),
                             _mesh_budget()) == []


def test_check_mesh_budget_replay_and_phase():
    from bench import check_mesh_budget
    viol = check_mesh_budget(_mesh_result(ok=False), _mesh_budget())
    assert any("replay" in v for v in viol)
    viol = check_mesh_budget(
        _mesh_result(phases={"probe_mirror": 9000.0}), _mesh_budget())
    assert any("probe_mirror" in v for v in viol)


def test_mesh_bench_reports_pod_and_per_shard(tmp_path):
    """bench.py --mesh-devices N end-to-end on the forced-host CPU mesh:
    records/sec/pod + records/sec/chip reported, per-shard probe
    breakdown present, restore+replay digests hold, and the committed
    mesh_cpu gate passes at smoke size."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--smoke",
         "--mesh-devices", "2", "--records", "65536", "--keys", "16384",
         "--batch-size", "16384", "--check"],
        capture_output=True, text=True, timeout=600, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, (proc.stdout, proc.stderr[-2000:])
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["ok"]
    assert result["records_per_sec_pod"] > 0
    assert result["records_per_sec_chip"] * 2 == pytest.approx(
        result["records_per_sec_pod"], rel=1e-6)
    d = result["details"]
    assert d["mesh_devices"] == 2 and d["restore_replay_ok"]
    assert [m["shard"] for m in d["shard_manifest"]] == [0, 1]


def _cep_result(mps=500.0, speedup=10.0, eq=True, auto="vectorized"):
    return {"value": mps, "ok": eq,
            "details": {"speedup_vs_interpreted": speedup,
                        "equivalence_ok": eq, "auto_engine": auto}}


def _cep_budget(**kw):
    b = {"min_matches_per_sec": 150.0, "min_speedup_vs_interpreted": 3.0,
         "min_speedup_smoke": 1.5}
    b.update(kw)
    return b


def test_check_cep_budget_pass():
    from bench import check_cep_budget
    assert check_cep_budget(_cep_result(), _cep_budget()) == []


def test_check_cep_budget_matches_floor_full_only():
    """The matches/sec floor gates FULL runs; smoke is one batch of fixed
    costs and only the relaxed speedup floor applies there."""
    from bench import check_cep_budget
    viol = check_cep_budget(_cep_result(mps=10.0), _cep_budget())
    assert len(viol) == 1 and "matches/sec" in viol[0]
    assert check_cep_budget(_cep_result(mps=10.0), _cep_budget(),
                            smoke=True) == []


def test_check_cep_budget_speedup_floor():
    """The acceptance bar: the batched kernel must beat the interpreted
    NFA by the budgeted factor (3x full, relaxed at smoke)."""
    from bench import check_cep_budget
    viol = check_cep_budget(_cep_result(speedup=2.0), _cep_budget())
    assert len(viol) == 1 and "speedup" in viol[0]
    # the same 2.0x PASSES the relaxed smoke floor...
    assert check_cep_budget(_cep_result(speedup=2.0), _cep_budget(),
                            smoke=True) == []
    # ...but a kernel losing outright fails even at smoke
    viol = check_cep_budget(_cep_result(speedup=0.9), _cep_budget(),
                            smoke=True)
    assert len(viol) == 1 and "speedup" in viol[0]


def test_check_cep_budget_unmeasured_speedup_is_a_violation():
    """An interpreted leg that recorded zero matches leaves the speedup
    None — the acceptance bar must not silently pass as unmeasured."""
    from bench import check_cep_budget
    viol = check_cep_budget(_cep_result(speedup=None), _cep_budget())
    assert any("unmeasured" in v for v in viol)
    viol = check_cep_budget(_cep_result(speedup=None), _cep_budget(),
                            smoke=True)
    assert any("unmeasured" in v for v in viol)


def test_check_cep_budget_equivalence_always_gates():
    """Divergent vectorized-vs-interpreted matches must never exit 0 —
    even at smoke size, even with every perf floor met."""
    from bench import check_cep_budget
    viol = check_cep_budget(_cep_result(eq=False), _cep_budget(),
                            smoke=True)
    assert any("equivalence" in v for v in viol)


def test_cep_bench_smoke_passes_gate():
    """bench.py --cep --smoke --check end-to-end on CPU: the vectorized
    kernel beats the interpreted NFA, auto calibration resolves, matches
    are equivalence-checked, and the committed cep_cpu gate passes."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--cep",
         "--smoke", "--records", "65536", "--keys", "65536",
         "--batch-size", "16384", "--check"],
        capture_output=True, text=True, timeout=600, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, (proc.stdout, proc.stderr[-2000:])
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    d = result["details"]
    assert result["ok"] and d["equivalence_ok"]
    assert d["auto_engine"] in ("vectorized", "interpreted")
    assert d["partials_high_water"] > 0
    assert d["speedup_vs_interpreted"] is not None
    assert d["degraded"] == 0


def _queryable_result(qps=148_000.0, p99=400.0, lag=1,
                      load_frac=0.94, live_eq=True, bin_eq=True,
                      errors=0, serve_p99=50.0):
    return {"value": qps,
            "details": {"lookups_per_sec": qps, "lookup_p50_ms": 4.5,
                        "lookup_p99_ms": p99,
                        "serve_p50_ms": 2.0, "serve_p99_ms": serve_p99,
                        "protocol": "binary", "routing": "client",
                        "max_replica_lag_checkpoints": lag,
                        "records_per_sec_under_load": 14_000_000.0,
                        "rps_under_load_frac": load_frac,
                        "live_equality_ok": live_eq,
                        "binary_json_equal_ok": bin_eq,
                        "lookup_errors": errors}}


def _queryable_budget():
    return {"min_lookups_per_sec": 100_000, "max_p99_ms": 2500,
            "max_replica_lag_checkpoints": 3,
            "min_rps_under_load_frac": 0.90}


def test_check_queryable_budget_pass():
    from bench import check_queryable_budget
    assert check_queryable_budget(_queryable_result(),
                                  _queryable_budget()) == []


def test_check_queryable_budget_floors_full_only():
    """qps + under-load-rps floors gate FULL runs (smoke is fixed-cost
    dominated); p99/lag ceilings and the equality check gate both."""
    from bench import check_queryable_budget
    viol = check_queryable_budget(_queryable_result(qps=100.0),
                                  _queryable_budget())
    assert len(viol) == 1 and "lookups/sec" in viol[0]
    assert check_queryable_budget(_queryable_result(qps=100.0),
                                  _queryable_budget(), smoke=True) == []
    viol = check_queryable_budget(_queryable_result(load_frac=0.7),
                                  _queryable_budget())
    assert len(viol) == 1 and "taxing the hot path" in viol[0]
    assert check_queryable_budget(_queryable_result(load_frac=0.7),
                                  _queryable_budget(), smoke=True) == []


def test_check_queryable_budget_p99_and_lag_ceilings():
    from bench import check_queryable_budget
    viol = check_queryable_budget(_queryable_result(p99=9000.0),
                                  _queryable_budget(), smoke=True)
    assert len(viol) == 1 and "p99" in viol[0]
    viol = check_queryable_budget(_queryable_result(lag=7),
                                  _queryable_budget(), smoke=True)
    assert len(viol) == 1 and "replica lag" in viol[0]


def test_check_queryable_budget_equality_and_errors_always_gate():
    """Wire values diverging from fire-time values, or lookups failing
    after pooled-client retries, must never exit 0 — even at smoke."""
    from bench import check_queryable_budget
    viol = check_queryable_budget(_queryable_result(live_eq=False),
                                  _queryable_budget(), smoke=True)
    assert any("diverge" in v for v in viol)
    viol = check_queryable_budget(_queryable_result(errors=3),
                                  _queryable_budget(), smoke=True)
    assert any("failed" in v for v in viol)
    # binary==JSON answer equality gates unconditionally too (ISSUE-13)
    viol = check_queryable_budget(_queryable_result(bin_eq=False),
                                  _queryable_budget(), smoke=True)
    assert any("binary" in v for v in viol)
    # an optional server-side serve-p99 ceiling is honored when present
    viol = check_queryable_budget(
        _queryable_result(serve_p99=9_000.0),
        {**_queryable_budget(), "max_serve_p99_ms": 1000}, smoke=True)
    assert any("serve p99" in v for v in viol)


def test_queryable_bench_smoke_passes_gate():
    """bench.py --queryable --smoke --check end-to-end on CPU: batched
    lookups over the real TCP protocol against the running window job,
    live values equal fire-time values, replica fed from the checkpoint
    stream, committed queryable_cpu gate passes."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--queryable",
         "--smoke", "--records", "65536", "--keys", "65536", "--check"],
        capture_output=True, text=True, timeout=600, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, (proc.stdout, proc.stderr[-2000:])
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    d = result["details"]
    assert result["ok"] and d["live_equality_ok"]
    assert d["binary_json_equal_ok"]
    assert d["lookup_errors"] == 0
    assert d["lookups"] > 0
    assert d["protocol"] == "binary" and d["routing"] == "client"
    assert d["serve_p99_ms"] is not None
    assert d["checkpoints_fed"] >= 1
    assert d["records_per_sec_under_load"] > 0


def _trace_detail(ratio=0.99, hot=20, ckpt=4, lat=1):
    return {"throughput_ratio": ratio, "hot_stage_spans": hot,
            "checkpoint_spans": ckpt, "latency_summaries": lat,
            "spans": hot + ckpt, "dropped_spans": 0}


def test_check_trace_budget_pass():
    from bench import check_trace_budget
    assert check_trace_budget(_trace_detail(),
                              {"min_throughput_ratio": 0.95}) == []


def test_check_trace_budget_throughput_floor():
    """Tracing-on must keep >= the budgeted fraction of tracing-off
    throughput (the <5% overhead acceptance).  Smoke-size runs skip the
    ratio floor only — fixed per-pass costs (compile, first fire)
    dominate a smoke pass and the on/off ratio is pure noise there."""
    from bench import check_trace_budget
    viol = check_trace_budget(_trace_detail(ratio=0.80),
                              {"min_throughput_ratio": 0.95})
    assert len(viol) == 1 and "tracing-on" in viol[0]
    assert check_trace_budget(_trace_detail(ratio=0.80),
                              {"min_throughput_ratio": 0.95},
                              smoke=True) == []
    # structural gates stay on at smoke size
    assert any("hot-stage" in v
               for v in check_trace_budget(_trace_detail(ratio=0.80, hot=0),
                                           {}, smoke=True))


def test_check_trace_budget_structural_checks_always_gate():
    """An artifact without hot-stage spans, checkpoint lifecycle spans or
    a latency summary is not a usable trace — never exit 0 on one."""
    from bench import check_trace_budget
    b = {"min_throughput_ratio": 0.95}
    assert any("hot-stage" in v
               for v in check_trace_budget(_trace_detail(hot=0), b))
    assert any("checkpoint" in v
               for v in check_trace_budget(_trace_detail(ckpt=0), b))
    assert any("latency" in v
               for v in check_trace_budget(_trace_detail(lat=0), b))


def test_trace_artifact_smoke(tmp_path):
    """bench.py --trace end-to-end at smoke size: the artifact is
    Perfetto-shaped trace-event JSON with hot-stage phase spans (the
    operator's own ``_phase`` vocabulary), checkpoint lifecycle spans and
    a latency histogram summary, and the tracing-on/off ratio is
    reported.  (The trace_cpu ratio gate itself runs with --check on the
    full bench — one smoke batch is fixed-cost noise.)"""
    out = tmp_path / "trace.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--smoke",
         "--records", "16384", "--keys", "2048", "--batch-size", "4096",
         "--checkpoint-every", "2", "--trace", str(out)],
        capture_output=True, text=True, timeout=600, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, (proc.stdout, proc.stderr[-2000:])
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    t = result["details"]["trace"]
    assert t["hot_stage_spans"] > 0 and t["checkpoint_spans"] > 0
    assert t["latency_summaries"] == 1 and t["throughput_ratio"] > 0
    with open(out) as f:
        artifact = json.load(f)
    assert artifact["displayTimeUnit"] == "ms"
    evs = artifact["traceEvents"]
    from flink_tpu.operators.window_agg import phase_span_name
    hot = {e["name"] for e in evs if e.get("cat") == "hot_stage"}
    assert hot and hot <= {phase_span_name(p)
                           for p in _operator_phase_names()}
    ckpt_names = {e["name"] for e in evs if e.get("cat") == "checkpoint"}
    assert {"checkpoint.trigger", "checkpoint.snapshot",
            "checkpoint"} <= ckpt_names
    assert artifact["otherData"]["latency_histograms"]["window_fire_ms"][
        "samples"] > 0
    # spans are the X/i/M trace-event dialect with µs timestamps
    assert all(e["ph"] in ("X", "i", "M") for e in evs)


def test_budget_file_shape():
    with open(os.path.join(REPO, "BENCH_BUDGET.json")) as f:
        budget = json.load(f)
    for tier in ("full", "smoke"):
        sec = budget[tier]
        assert sec["min_rps"] > 0
        assert sec["max_p99_ms"] > 0
        assert "probe_mirror" in sec["max_phase_ms"]
    # checkpoint-under-backpressure budget (bench.py --checkpoint-interval)
    cb = budget["checkpoint_backpressure"]
    assert cb["max_duration_ms"] > 0 and cb["min_completed"] >= 1
    # the tracing-overhead gate (bench.py --trace --check): tracing-on
    # must keep >= 95% of tracing-off throughput
    tr = budget["trace_cpu"]
    assert 0.95 <= tr["min_throughput_ratio"] <= 1.0
    # CPU-forced full runs carry the pipelined-hot-path acceptance keys
    full_cpu = budget["full_cpu"]
    assert full_cpu["min_vs_numpy"] >= 1.0
    assert 0 < full_cpu["max_probe_mirror_frac"] <= 1.0
    # the full_cpu floor must catch losing the deferred lane (~1.6M rec/s
    # measured scatter fallback on the reference host)
    assert full_cpu["min_rps"] > 2_000_000
    # the mesh gate (bench.py --mesh-devices --check on CPU)
    mesh = budget["mesh_cpu"]
    assert mesh["min_rps_pod"] > 0
    assert 0 < mesh["max_shard_probe_share"] <= 1.0
    assert "probe_mirror" in mesh["max_phase_ms"]
    # the serving-tier gate (bench.py --queryable --check)
    qs = budget["queryable_cpu"]
    assert qs["min_lookups_per_sec"] >= 100_000    # the ISSUE-13 floor
    assert qs["max_p99_ms"] > 0
    assert qs["max_replica_lag_checkpoints"] >= 1
    assert 0.90 <= qs["min_rps_under_load_frac"] < 1.0
    # the vectorized-CEP gate (bench.py --cep --check)
    cep = budget["cep_cpu"]
    assert cep["min_matches_per_sec"] > 0
    assert cep["min_speedup_vs_interpreted"] >= 3.0
    assert 0 < cep["min_speedup_smoke"] <= cep["min_speedup_vs_interpreted"]
    # the scenario-suite gates (bench.py --scenario --check, ISSUE-15):
    # every scenario must demand >= 1 autoscaler reaction; perf floors
    # exist for the full tier (exactly-once gates unconditionally in code)
    for sec in ("scenario_fraud_cpu", "scenario_session_cpu",
                "scenario_feature_cpu"):
        sc = budget[sec]
        assert sc["min_rescales"] >= 1
        assert sc["min_peak_rps"] > 0
        assert sc["max_p99_ms"] > 0
        assert sc["min_lookups_per_sec"] > 0
    # real-accelerator runs gate against the *_device sections (ROADMAP
    # item 2's second half: device rounds regress loudly, like CPU ones)
    for tier in ("full_device", "smoke_device"):
        sec = budget[tier]
        assert sec["min_rps"] > 0 and sec["max_p99_ms"] > 0


def _operator_phase_names():
    """The operator's ``_phase("...")`` names, scraped from the source —
    the profile artifact's key vocabulary."""
    import re
    src = os.path.join(REPO, "flink_tpu", "operators", "window_agg.py")
    with open(src) as f:
        names = set(re.findall(r"_phase\(\"([a-z_]+)\"\)", f.read()))
    assert names, "no _phase(...) sites found in window_agg.py"
    return names


def test_profile_artifact_produced_and_keys_match(tmp_path):
    """bench.py --profile writes the per-phase JSON artifact (VERDICT #10)
    and its phase keys are exactly the operator's ``_phase`` names, each
    with its ``<phase>_cpu`` beside it, plus the dispatch's two thread
    hand-offs (kept outside ``_phase``: they cross threads) and the
    bench-level snapshot_total rollup."""
    out = tmp_path / "profile.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--smoke",
         "--records", "16384", "--keys", "2048", "--batch-size", "4096",
         "--profile", str(out)],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, (proc.stdout, proc.stderr[-2000:])
    assert out.exists(), "--profile did not write the artifact"
    with open(out) as f:
        prof = json.load(f)
    names = _operator_phase_names()
    handoffs = {"dispatch_handoff", "dispatch_return"}
    allowed = (names | {n + "_cpu" for n in names} | handoffs
               | {"snapshot_total"})
    for section in ("phase_ns", "phases_ms"):
        keys = set(prof[section])
        assert keys <= allowed, f"unknown phase keys: {keys - allowed}"
        assert "probe_mirror" in keys or "probe" in keys
        assert {"process_batch", "process_batch_cpu"} <= keys
        if "device_dispatch" in keys:      # a lane that dispatches
            assert {"launch", "launch_cpu"} | handoffs <= keys
        assert all(k + "_cpu" in keys for k in keys & names)
    assert prof["phase_ns"].get("probe_mirror", 0) > 0 or \
        prof["phase_ns"].get("probe", 0) > 0
    assert prof["trace_annotation"] == "window_agg.device_step"
    assert "phase_bytes" in prof and "elapsed_ms" in prof


def test_inject_wedge_smoke_exercises_shared_recovery_path(tmp_path):
    """bench.py --inject-wedge drives the runtime/bench SHARED recovery
    path (device_health watchdog -> quarantine -> degrade -> heal ->
    checkpoint-aligned re-promotion) end-to-end on CPU and exits 0 only
    when the full cycle ran with digest-identical fires."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--inject-wedge"],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, (proc.stdout, proc.stderr[-2000:])
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["ok"] and result["digest_match"]
    assert result["snapshot_during_quarantine"]
    hs = result["device_health"]
    assert hs["quarantines"] == 1 and hs["heals"] == 1
    assert hs["watchdog_timeouts"] == 1
    assert hs["quarantine_migrations"] == 1 and hs["repromotions"] == 1
    assert hs["state"] == "healthy" and hs["degraded"] == 0


def _incr_result(**kw):
    r = {"ok": True, "n_keys": 1_000_000, "churn_keys": 100_000,
         "incremental_checkpoints": 5, "full_snapshot_bytes": 16_000_000,
         "increment_bytes_max": 1_600_000, "bytes_ratio": 0.10,
         "increments_per_base": 5, "compactions": 0,
         "recovery_ms": 900.0, "digest_match": True}
    r.update(kw)
    return r


def _incr_budget(**kw):
    b = {"max_bytes_ratio": 0.25, "max_recovery_ms": 30000,
         "min_incremental_checkpoints": 1}
    b.update(kw)
    return b


def test_check_incremental_budget_pass():
    from bench import check_incremental_budget
    assert check_incremental_budget(_incr_result(), _incr_budget()) == []


def test_check_incremental_budget_bytes_ratio_ceiling():
    from bench import check_incremental_budget
    viol = check_incremental_budget(_incr_result(bytes_ratio=0.40),
                                    _incr_budget())
    assert len(viol) == 1 and "25%" in viol[0]


def test_check_incremental_budget_digest_always_gates():
    """Digest inequality and zero delta cuts violate even in smoke and
    even with an EMPTY budget section — a delta format that resolves to
    different state or silently re-bases every cut never exits 0."""
    from bench import check_incremental_budget
    viol = check_incremental_budget(_incr_result(digest_match=False), {},
                                    smoke=True)
    assert any("digest" in v for v in viol)
    viol = check_incremental_budget(_incr_result(incremental_checkpoints=0),
                                    {}, smoke=True)
    assert any("re-based" in v for v in viol)


def test_check_incremental_budget_recovery_ceiling_full_only():
    from bench import check_incremental_budget
    res = _incr_result(recovery_ms=90_000.0)
    assert check_incremental_budget(res, _incr_budget(), smoke=True) == []
    viol = check_incremental_budget(res, _incr_budget(), smoke=False)
    assert len(viol) == 1 and "recovery" in viol[0]


def test_checkpoint_incremental_budget_section_present():
    """BENCH_BUDGET.json carries the ISSUE-16 gate with the acceptance
    ceiling: delta bytes <= 25% of full at <=10% churn."""
    with open(os.path.join(REPO, "BENCH_BUDGET.json")) as f:
        sec = json.load(f)["checkpoint_incremental"]
    assert 0 < sec["max_bytes_ratio"] <= 0.25
    assert sec["max_recovery_ms"] > 0
    assert sec["min_incremental_checkpoints"] >= 1


def test_incremental_bench_smoke_passes_gate():
    """The real incremental leg (smoke size) must hold its own budget:
    delta cuts happen, bytes ratio inside the ceiling, chain restore
    digest-identical."""
    from bench import check_incremental_budget, \
        run_incremental_checkpoint_bench
    result = run_incremental_checkpoint_bench(smoke=True)
    with open(os.path.join(REPO, "BENCH_BUDGET.json")) as f:
        budget = json.load(f)["checkpoint_incremental"]
    assert result["ok"], result
    assert check_incremental_budget(result, budget, smoke=True) == []
    assert result["increments_per_base"] >= 1
    assert result["bytes_ratio"] <= budget["max_bytes_ratio"]


def test_checkpoint_interval_completes_within_budget_under_backpressure():
    """bench.py --checkpoint-interval injects SlowConsumer + SlowDisk
    backpressure and asserts checkpoints (aligned-with-timeout escalation
    enabled) still complete within the checkpoint_backpressure budget,
    reporting duration + persisted in-flight bytes — exits 0 only when a
    checkpoint completed in budget with exactly-once sums."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--checkpoint-interval", "50"],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, (proc.stdout, proc.stderr[-2000:])
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["ok"] and result["exactly_once"]
    with open(os.path.join(REPO, "BENCH_BUDGET.json")) as f:
        budget_all = json.load(f)
    budget = budget_all["checkpoint_backpressure"]
    assert result["completed_checkpoints"] >= budget["min_completed"]
    assert result["max_duration_ms"] <= budget["max_duration_ms"]
    # backpressure was REAL (the chaos schedules actually persisted
    # in-flight data) — otherwise the run proves nothing
    assert result["unaligned_checkpoints"] >= 1
    assert result["persisted_inflight_bytes_total"] > 0
    # the ISSUE-16 incremental leg rides the same flag: delta cuts land,
    # chain restore is digest-identical, bytes ratio inside the ceiling
    inc = result["incremental"]
    assert inc["digest_match"] and inc["incremental_checkpoints"] >= 1
    assert inc["bytes_ratio"] <= budget_all["checkpoint_incremental"][
        "max_bytes_ratio"]


@pytest.mark.slow
def test_smoke_bench_passes_gate():
    """The committed budget must hold on this host: run the real smoke
    bench end-to-end under --check."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--smoke",
         "--check"],
        capture_output=True, text=True, timeout=900, cwd=REPO)
    assert proc.returncode == 0, (proc.stdout, proc.stderr[-2000:])


# ---------------------------------------------------------------------------
# --autoscale (ISSUE-14): reactive autoscaler under a diurnal load curve
# ---------------------------------------------------------------------------

def _rescale_result(state="Finished", lost=0, dup=0, rescales=2,
                    rollbacks=0, latency=1500.0, recovery=8000.0):
    return {"state": state, "records_lost": lost,
            "records_duplicated": dup, "rescales": rescales,
            "rollbacks": rollbacks, "rescale_latency_ms": latency,
            "recovery_ms": recovery}


def _rescale_budget(**kw):
    b = {"min_rescales": 1, "max_rollbacks": 0,
         "max_rescale_latency_ms": 20000, "max_recovery_ms": 60000}
    b.update(kw)
    return b


def test_check_rescale_budget_pass():
    from bench import check_rescale_budget
    assert check_rescale_budget(_rescale_result(), _rescale_budget()) == []


def test_check_rescale_budget_exactly_once_always_gates():
    """Lost/duplicated records and a non-finished job violate even with an
    EMPTY budget section — a lossy rescale must never exit 0 because no
    perf ceiling was configured."""
    from bench import check_rescale_budget
    assert any("records_lost" in v
               for v in check_rescale_budget(_rescale_result(lost=3), {}))
    assert any("records_duplicated" in v
               for v in check_rescale_budget(_rescale_result(dup=1), {}))
    assert any("did not finish" in v
               for v in check_rescale_budget(
                   _rescale_result(state="Failed"), {}))


def test_check_rescale_budget_floors_and_ceilings():
    from bench import check_rescale_budget
    b = _rescale_budget()
    assert any("rescales" in v for v in check_rescale_budget(
        _rescale_result(rescales=0), b))
    assert any("rollbacks" in v for v in check_rescale_budget(
        _rescale_result(rollbacks=1), b))
    assert any("rescale latency" in v for v in check_rescale_budget(
        _rescale_result(latency=30000.0), b))
    assert any("recovery" in v for v in check_rescale_budget(
        _rescale_result(recovery=90000.0), b))
    # recovery ceiling is full-run only (smoke streams are too short for
    # a meaningful drain measurement)
    assert check_rescale_budget(_rescale_result(recovery=90000.0), b,
                                smoke=True) == []


def _scenario_result(state="Finished", control="Finished", lost=0, dup=0,
                     digest=True, rescales=2, rollbacks=0, cross=(),
                     committed=None, peak=2500.0, p99=5000.0, lps=400.0):
    return {"scenario": "fraud_detection", "state": state,
            "control_state": control, "records_lost": lost,
            "records_duplicated": dup, "digest_match": digest,
            "rescales": rescales, "rollbacks": rollbacks,
            "cross_check_violations": list(cross),
            "committed_rows": committed if committed is not None
            else {"alerts": 575},
            "peak_records_per_sec": peak, "latency_p99_ms": p99,
            "queryable": {"lookups_per_sec": lps}}


def _scenario_budget(**kw):
    b = {"min_rescales": 1, "min_peak_rps": 1000, "max_p99_ms": 30000,
         "min_lookups_per_sec": 60}
    b.update(kw)
    return b


def test_check_scenario_budget_pass():
    from bench import check_scenario_budget
    assert check_scenario_budget(_scenario_result(),
                                 _scenario_budget()) == []


def test_check_scenario_budget_exactly_once_always_gates():
    """Lost/duplicated/digest-mismatch/cross-check/no-output violate even
    with an EMPTY budget section and in smoke — a lossy scenario must
    never exit 0 because no perf floor was configured."""
    from bench import check_scenario_budget
    assert any("records_lost" in v for v in check_scenario_budget(
        _scenario_result(lost=3), {}, smoke=True))
    assert any("records_duplicated" in v for v in check_scenario_budget(
        _scenario_result(dup=1), {}, smoke=True))
    assert any("digest" in v for v in check_scenario_budget(
        _scenario_result(digest=False), {}, smoke=True))
    assert any("did not finish" in v for v in check_scenario_budget(
        _scenario_result(state="Failed"), {}, smoke=True))
    assert any("control" in v for v in check_scenario_budget(
        _scenario_result(control="Canceled"), {}, smoke=True))
    assert any("TUMBLE" in v for v in check_scenario_budget(
        _scenario_result(cross=["SQL TUMBLE cross-check: diverged"]), {},
        smoke=True))
    assert any("no committed output" in v for v in check_scenario_budget(
        _scenario_result(committed={"alerts": 0}), {}, smoke=True))


def test_check_scenario_budget_floors_and_ceilings():
    from bench import check_scenario_budget
    b = _scenario_budget()
    assert any("rescales" in v for v in check_scenario_budget(
        _scenario_result(rescales=0), b))
    assert any("peak" in v for v in check_scenario_budget(
        _scenario_result(peak=100.0), b))
    assert any("p99" in v for v in check_scenario_budget(
        _scenario_result(p99=60000.0), b))
    assert any("queryable" in v for v in check_scenario_budget(
        _scenario_result(lps=1.0), b))
    assert any("rollbacks" in v for v in check_scenario_budget(
        _scenario_result(rollbacks=2), _scenario_budget(max_rollbacks=0)))
    # perf floors are full-run only; exactly-once still gates in smoke
    assert check_scenario_budget(
        _scenario_result(peak=100.0, p99=60000.0, lps=1.0), b,
        smoke=True) == []


@pytest.mark.slow
def test_scenario_bench_smoke_passes_gate(tmp_path):
    """bench.py --scenario fraud_detection --smoke --check end-to-end on
    CPU: the fraud scenario survives its peak nemeses exactly-once
    (digest == unfaulted control), the autoscaler reacts on the curve,
    and the committed scenario_fraud_cpu gate passes."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--scenario", "fraud_detection", "--smoke", "--records", "30000",
         "--check"],
        capture_output=True, text=True, timeout=900, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, (proc.stdout, proc.stderr[-3000:])
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["ok"]
    (s,) = result["scenarios"]
    assert s["scenario"] == "fraud_detection"
    assert s["state"] == "Finished" and s["control_state"] == "Finished"
    assert s["records_lost"] == 0 and s["records_duplicated"] == 0
    assert s["digest_match"] and s["rescales"] >= 1
    assert s["committed_rows"]["alerts"] > 0
    assert s["queryable"]["lookups"] > 0


def test_autoscale_bench_smoke_passes_gate():
    """bench.py --autoscale --smoke --check end-to-end on CPU: the
    autoscaler reacts to the diurnal curve (>= 1 rescale via an unaligned
    cut + channel-state redistribution) with ZERO records lost or
    duplicated, and the committed rescale_cpu gate passes."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--autoscale",
         "--smoke", "--records", "80000", "--check"],
        capture_output=True, text=True, timeout=600, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, (proc.stdout, proc.stderr[-2000:])
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["ok"] and result["state"] == "Finished"
    assert result["records_lost"] == 0
    assert result["records_duplicated"] == 0
    assert result["rescales"] >= 1
    assert max(result["parallelism_path"]) >= 4
    assert result["rescale_latency_ms"] is not None


def _ha_result(state="FINISHED", control="Finished", epochs=(1, 2),
               pointer_fenced=True, commit_fenced=True, lost=0, dup=0,
               digest=True, committed=None, recovery=4000.0):
    return {"scenario": "fraud_detection", "state": state,
            "control_state": control, "leader_epochs": list(epochs),
            "stale_pointer_rejected": pointer_fenced,
            "stale_commit_fenced": commit_fenced,
            "records_lost": lost, "records_duplicated": dup,
            "digest_match": digest,
            "committed_rows": committed if committed is not None
            else {"alerts": 575},
            "recovery_ms": recovery}


def _ha_budget(**kw):
    b = {"max_recovery_ms": 30000}
    b.update(kw)
    return b


def test_check_ha_budget_pass():
    from bench import check_ha_budget
    assert check_ha_budget(_ha_result(), _ha_budget()) == []


def test_check_ha_budget_fencing_and_exactly_once_always_gate():
    """A zombie completing a checkpoint or committing a 2PC transaction,
    a non-advancing epoch, lost/duplicated rows, a digest mismatch or no
    output violate even with an EMPTY budget section and in smoke — a
    split-brain run must never exit 0 because no ceiling was
    configured."""
    from bench import check_ha_budget
    assert any("NOT fenced by the HA store" in v for v in check_ha_budget(
        _ha_result(pointer_fenced=False), {}, smoke=True))
    assert any("2PC" in v for v in check_ha_budget(
        _ha_result(commit_fenced=False), {}, smoke=True))
    assert any("leader epoch" in v for v in check_ha_budget(
        _ha_result(epochs=(1, 1)), {}, smoke=True))
    assert any("leader epoch" in v for v in check_ha_budget(
        _ha_result(epochs=(2,)), {}, smoke=True))
    assert any("records_lost" in v for v in check_ha_budget(
        _ha_result(lost=3), {}, smoke=True))
    assert any("records_duplicated" in v for v in check_ha_budget(
        _ha_result(dup=1), {}, smoke=True))
    assert any("digest" in v for v in check_ha_budget(
        _ha_result(digest=False), {}, smoke=True))
    assert any("did not finish" in v for v in check_ha_budget(
        _ha_result(state="FAILED"), {}, smoke=True))
    assert any("control" in v for v in check_ha_budget(
        _ha_result(control="Canceled"), {}, smoke=True))
    assert any("no committed output" in v for v in check_ha_budget(
        _ha_result(committed={"alerts": 0}), {}, smoke=True))


def test_check_ha_budget_recovery_ceiling_full_only():
    from bench import check_ha_budget
    b = _ha_budget(max_recovery_ms=1000)
    assert any("recovery" in v for v in check_ha_budget(
        _ha_result(recovery=5000.0), b))
    # smoke hosts jitter too much for a wall-clock gate
    assert check_ha_budget(_ha_result(recovery=5000.0), b,
                           smoke=True) == []


def test_ha_budget_section_present():
    with open(os.path.join(REPO, "BENCH_BUDGET.json")) as f:
        budget = json.load(f)
    ha = budget["ha_cpu"]
    assert ha["max_recovery_ms"] > 0


@pytest.mark.slow
def test_ha_kill_bench_smoke_passes_gate():
    """bench.py --ha-kill --smoke --check end-to-end on CPU: the leader
    is killed at the peak and runs on as a zombie, the standby takes
    over at epoch+1, both stale-epoch fences hold, and the committed
    ha_cpu gate passes with a digest identical to the control."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--ha-kill",
         "--smoke", "--check"],
        capture_output=True, text=True, timeout=600, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, (proc.stdout, proc.stderr[-3000:])
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["ok"]
    res = result["ha_kill"]
    assert res["state"] == "FINISHED"
    assert res["control_state"] == "Finished"
    assert res["leader_epochs"][1] == res["leader_epochs"][0] + 1
    assert res["stale_pointer_rejected"] and res["stale_commit_fenced"]
    assert res["records_lost"] == 0 and res["records_duplicated"] == 0
    assert res["digest_match"]
    assert res["restore_source"] == "ha-pointer"
