"""Pytest marker audit (ISSUE-4 CI satellite).

Two invariants keep the two-tier test scheme honest:

1. Every marker used anywhere under ``tests/`` is DECLARED in
   ``pyproject.toml`` (or a pytest builtin) — an unknown marker silently
   selects nothing, so a typo like ``choas`` would quietly drop a test
   from every ``-m`` expression.
2. The ``chaos`` suite stays visible to the tier-1 command
   (``-m 'not slow'``): at least a meaningful share of chaos-marked
   tests must NOT also be slow-marked, or fault-injection coverage
   silently migrates out of the gate everyone runs.
"""

import re
import sys
from pathlib import Path

TESTS = Path(__file__).parent
REPO = TESTS.parent

#: pytest's own marks — always legal without declaration
BUILTIN_MARKS = {"parametrize", "skip", "skipif", "xfail", "usefixtures",
                 "filterwarnings", "tryfirst", "trylast"}


def _marker_entries():
    """The declared marker lines from pyproject.toml (`name: description`
    strings), parsed with tomllib when available (3.11+), regex on 3.10."""
    text = (REPO / "pyproject.toml").read_text()
    try:
        import tomllib
    except ImportError:          # py310: stdlib tomllib is 3.11+
        block = re.search(r"markers\s*=\s*\[(.*?)\]", text, re.S).group(1)
        return [a or b for a, b in
                re.findall(r"\"([^\"]+)\"|'([^']+)'", block)]
    return tomllib.loads(text)["tool"]["pytest"]["ini_options"]["markers"]


def _declared_markers():
    return {ln.split(":", 1)[0].strip() for ln in _marker_entries()}


def _marks_used():
    """marker name -> set of files using it, scraped from the suite."""
    used = {}
    for path in sorted(TESTS.glob("*.py")):
        src = path.read_text()
        for m in re.finditer(r"pytest\.mark\.([A-Za-z_][A-Za-z0-9_]*)", src):
            used.setdefault(m.group(1), set()).add(path.name)
    return used


def test_every_used_marker_is_declared():
    declared = _declared_markers()
    unknown = {name: sorted(files)
               for name, files in _marks_used().items()
               if name not in declared and name not in BUILTIN_MARKS}
    assert not unknown, (
        f"markers used but not declared in pyproject.toml: {unknown} — "
        f"declare them under [tool.pytest.ini_options].markers or fix the "
        f"typo (an unknown marker silently drops tests from -m selections)")


def test_chaos_suite_collects_under_tier1():
    """Every chaos-suite FILE must contribute tests to the tier-1 run:
    a file whose chaos tests are all slow-marked has silently left the
    gate.  Verified by real collection, not regex: collect with the
    tier-1 expression and require chaos tests from each chaos file."""
    import subprocess

    mark_re = re.compile(r"^pytestmark\s*=.*\bchaos\b|^@pytest\.mark\.chaos",
                         re.M)
    chaos_files = sorted(p.name for p in TESTS.glob("*.py")
                         if mark_re.search(p.read_text()))
    assert chaos_files, "no chaos-marked files found at all"
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "--collect-only", "-q",
         "-m", "chaos and not slow", "-p", "no:cacheprovider",
         *[str(TESTS / f) for f in chaos_files]],
        capture_output=True, text=True, timeout=300, cwd=str(REPO))
    assert proc.returncode == 0, proc.stderr[-2000:]
    collected = proc.stdout
    for f in chaos_files:
        assert f"{f}::" in collected, \
            (f"{f} contributes no tests to the tier-1 chaos selection "
             f"(-m 'chaos and not slow') — its whole chaos coverage is "
             f"slow-gated")


def test_mesh_suite_collects_under_tier1():
    """The mesh-sharded hot path's suites (ISSUE-6) must contribute tests
    to the tier-1 run under ``JAX_PLATFORMS=cpu``: the conftest forces an
    8-device virtual CPU mesh, so multi-device sharding is exercised by
    the gate everyone runs — a slow-mark or cpu-skip sweep that silently
    drops them fails here.  Verified by real collection, not regex."""
    import subprocess

    mesh_files = ["test_mesh_invariance.py", "test_mesh_runtime.py",
                  "test_parallel.py"]
    for f in mesh_files:
        assert (TESTS / f).exists(), f
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "--collect-only", "-q",
         "-m", "not slow", "-p", "no:cacheprovider",
         *[str(TESTS / f) for f in mesh_files]],
        capture_output=True, text=True, timeout=300, cwd=str(REPO),
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    for f in mesh_files:
        assert f"{f}::" in proc.stdout, \
            (f"{f} contributes no tests to the tier-1 selection "
             f"(-m 'not slow' under JAX_PLATFORMS=cpu) — mesh sharding "
             f"coverage left the gate")


@__import__("functools").lru_cache(maxsize=None)
def _window_lanes_tier1_ids():
    """Node ids ``tests/test_window_lanes.py`` contributes to the tier-1
    selection (``-m 'not slow'`` under ``JAX_PLATFORMS=cpu``)."""
    import subprocess

    f = "test_window_lanes.py"
    assert (TESTS / f).exists(), f
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "--collect-only", "-q",
         "-m", "not slow", "-p", "no:cacheprovider", str(TESTS / f)],
        capture_output=True, text=True, timeout=300, cwd=str(REPO),
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    return [line for line in proc.stdout.splitlines() if f"{f}::" in line]


def test_window_lanes_suite_collects_under_tier1():
    """The lane suite is what checks every remaining fold lane against
    the plain reference (and stands in for the count of the device-probe
    and fused-step suites that went with their code): at least 40 of its
    cases must stay in the tier-1 run."""
    ids = _window_lanes_tier1_ids()
    assert len(ids) >= 40, \
        (f"test_window_lanes.py contributes {len(ids)} tests to the tier-1 "
         f"selection — lane coverage left the gate")


def test_cep_vectorized_suite_collects_under_tier1():
    """The vectorized CEP suite (ISSUE-8) must contribute tests to the
    tier-1 run under ``JAX_PLATFORMS=cpu`` — the numpy kernel is the
    bit-identical portable path, so the equivalence corpus never leaves
    the gate."""
    import subprocess

    f = "test_cep_vectorized.py"
    assert (TESTS / f).exists(), f
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "--collect-only", "-q",
         "-m", "not slow", "-p", "no:cacheprovider", str(TESTS / f)],
        capture_output=True, text=True, timeout=300, cwd=str(REPO),
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert f"{f}::" in proc.stdout, \
        (f"{f} contributes no tests to the tier-1 selection — the "
         f"vectorized CEP equivalence corpus left the gate")


def test_queryable_suite_collects_under_tier1():
    """The queryable serving tier's suite (ISSUE-9) must contribute tests
    to the tier-1 run under ``JAX_PLATFORMS=cpu`` — live-read bit-equality
    (mesh 1v2 included), replica staleness/chaos, and the wire protocol
    all run on the CPU backend, so a slow-mark sweep that silently drops
    them fails here."""
    import subprocess

    f = "test_queryable_serving.py"
    assert (TESTS / f).exists(), f
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "--collect-only", "-q",
         "-m", "not slow", "-p", "no:cacheprovider", str(TESTS / f)],
        capture_output=True, text=True, timeout=300, cwd=str(REPO),
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert f"{f}::" in proc.stdout, \
        (f"{f} contributes no tests to the tier-1 selection — the serving "
         f"tier's read-path coverage left the gate")


def test_queryable_scale_suite_collects_under_tier1():
    """The production-QPS serving suite (ISSUE-13) must contribute tests
    to the tier-1 run under ``JAX_PLATFORMS=cpu`` — binary codec
    round-trips, routing-table correctness, cache invalidation,
    per-worker serving e2e and protocol negotiation all run on the CPU
    backend, so a slow-mark sweep that silently drops them fails here."""
    import subprocess

    f = "test_queryable_scale.py"
    assert (TESTS / f).exists(), f
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "--collect-only", "-q",
         "-m", "not slow", "-p", "no:cacheprovider", str(TESTS / f)],
        capture_output=True, text=True, timeout=300, cwd=str(REPO),
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert f"{f}::" in proc.stdout, \
        (f"{f} contributes no tests to the tier-1 selection — the "
         f"production-QPS read-path coverage left the gate")


def test_tracing_suite_collects_under_tier1():
    """The end-to-end tracing suite (ISSUE-10) must contribute tests to
    the tier-1 run under ``JAX_PLATFORMS=cpu`` — span-journal semantics,
    marker→histogram plumbing and the ProcessCluster merged timeline all
    run on the CPU backend, so a slow-mark sweep that silently drops
    them fails here."""
    import subprocess

    f = "test_tracing.py"
    assert (TESTS / f).exists(), f
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "--collect-only", "-q",
         "-m", "not slow", "-p", "no:cacheprovider", str(TESTS / f)],
        capture_output=True, text=True, timeout=300, cwd=str(REPO),
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert f"{f}::" in proc.stdout, \
        (f"{f} contributes no tests to the tier-1 selection — the "
         f"observability suite left the gate")


def test_window_lanes_suite_keeps_every_lane_in_tier1():
    """A slow-mark sweep that drops one lane's cases (the mesh's, say)
    leaves the count above its floor: every lane must keep a reference
    case AND a restore case in the tier-1 run."""
    ids = _window_lanes_tier1_ids()
    for lane in ("device", "host_scatter", "host_deferred", "host_numpy",
                 "device_pipelined", "mesh2_device"):
        # ids: ...reference[<lane>-<job>-<order>], ...lanes[<job>-<a>-<b>]
        assert any("test_lane_delivers_the_reference[" + lane + "-" in i
                   for i in ids), f"no reference case left for {lane!r}"
        assert any("test_snapshot_restores_across_lanes[" in i
                   and i.endswith("-" + lane + "]") for i in ids), \
            f"no restore case left into {lane!r}"


def test_rescale_under_fire_suite_collects_under_tier1():
    """The rescale-under-fire suite (ISSUE-14) must contribute tests to
    the tier-1 run under ``JAX_PLATFORMS=cpu`` — channel-state
    redistribution route-by-key correctness, the autoscaler's hysteresis
    and the chaos-proof rescale lifecycle (kill / rollback / re-trigger)
    all run on the CPU backend, so a slow-mark sweep that silently drops
    them fails here."""
    import subprocess

    f = "test_rescale_under_fire.py"
    assert (TESTS / f).exists(), f
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "--collect-only", "-q",
         "-m", "not slow", "-p", "no:cacheprovider", str(TESTS / f)],
        capture_output=True, text=True, timeout=300, cwd=str(REPO),
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert f"{f}::" in proc.stdout, \
        (f"{f} contributes no tests to the tier-1 selection — the rescale "
         f"lifecycle's exactly-once coverage left the gate")


def test_scenarios_suite_collects_under_tier1():
    """The scenario suite (ISSUE-15) must contribute tests to the tier-1
    run under ``JAX_PLATFORMS=cpu`` — the per-scenario exactly-once-
    under-kill acceptances vs the unfaulted control, the CEP/session
    rescale split/merge units, the two-phase-commit sink lifecycle and
    the SQL-vs-datastream cross-check all run on the CPU backend, so a
    slow-mark sweep that silently drops them fails here."""
    import subprocess

    f = "test_scenarios.py"
    assert (TESTS / f).exists(), f
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "--collect-only", "-q",
         "-m", "not slow", "-p", "no:cacheprovider", str(TESTS / f)],
        capture_output=True, text=True, timeout=300, cwd=str(REPO),
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert f"{f}::" in proc.stdout, \
        (f"{f} contributes no tests to the tier-1 selection — the "
         f"scenario suite's exactly-once coverage left the gate")


def test_incremental_checkpoint_suite_collects_under_tier1():
    """The incremental-checkpoint suite (ISSUE-16) must contribute tests
    to the tier-1 run under ``JAX_PLATFORMS=cpu`` — the digest-identical
    chain-restore acceptances per state tier, the bytes-scale-with-churn
    budget, the storage chain/compaction/retention semantics and the
    MiniCluster sub-second end-to-end all run on the CPU backend, so a
    slow-mark sweep that silently drops them fails here."""
    import subprocess

    f = "test_incremental_checkpoints.py"
    assert (TESTS / f).exists(), f
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "--collect-only", "-q",
         "-m", "not slow", "-p", "no:cacheprovider", str(TESTS / f)],
        capture_output=True, text=True, timeout=300, cwd=str(REPO),
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert f"{f}::" in proc.stdout, \
        (f"{f} contributes no tests to the tier-1 selection — the "
         f"incremental-checkpoint restore coverage left the gate")


def test_ha_suite_collects_under_tier1():
    """The coordinator-HA suite (ISSUE-20) must contribute tests to the
    tier-1 run under ``JAX_PLATFORMS=cpu`` — the lease/epoch units, the
    store/worker/data-plane/2PC stale-epoch fences, the pinned-retention
    and resolve_restore recovery semantics and the kill-the-leader
    scenario acceptance all run on the CPU backend, so a slow-mark sweep
    that silently drops them fails here."""
    import subprocess

    f = "test_ha.py"
    assert (TESTS / f).exists(), f
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "--collect-only", "-q",
         "-m", "not slow", "-p", "no:cacheprovider", str(TESTS / f)],
        capture_output=True, text=True, timeout=300, cwd=str(REPO),
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert f"{f}::" in proc.stdout, \
        (f"{f} contributes no tests to the tier-1 selection — the "
         f"coordinator-HA fencing coverage left the gate")


def test_marker_declarations_have_descriptions():
    """Each declared marker carries a description (the `name: text` form)
    so `pytest --markers` documents the tiers."""
    entries = _marker_entries()
    assert entries
    for entry in entries:
        assert ":" in entry and entry.split(":", 1)[1].strip(), \
            f"marker {entry!r} lacks a description"
