"""chip_smoke.py off the chip: its job and its reference agree at a tiny
size on the CPU, it refuses to pass without a TPU, and the pieces of the
bring-up it leans on hold (mesh size, compile-cache placement, one process
for each chip)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402


@pytest.fixture
def restore_cache_config():
    import jax

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)


def test_job_matches_reference_at_tiny_size():
    ev = chip_smoke.Events(seed=3, n_keys=512, batch=1024, n_batches=14)
    sink, op = chip_smoke.run_local(ev)
    cell, got = ev.check_rows(sink)
    assert cell.size == ev.ref_cells.size > 512
    chip_smoke.check_healthy([op])
    # the reference check has teeth: a dropped row fails it
    sink.batches.pop()
    with pytest.raises(RuntimeError, match="differs from the reference"):
        ev.check_rows(sink)


def test_main_fails_without_a_tpu(capsys, restore_cache_config):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert "platform=cpu" in out and '"ok"' not in out


def test_result_line_has_exactly_the_contract_keys():
    import json

    import jax

    devices = jax.devices()
    line = json.loads(chip_smoke.result_line(devices))
    assert line == {"ok": True,
                    "device": {"platform": "cpu",
                               "kind": devices[0].device_kind, "count": 8}}


def test_make_mesh_does_not_shrink():
    from flink_tpu.datastream.api import StreamExecutionEnvironment
    from flink_tpu.parallel.mesh import make_mesh

    assert make_mesh(n_devices=8).devices.size == 8
    with pytest.raises(ValueError, match="only 8"):
        make_mesh(n_devices=9)
    with pytest.raises(ValueError, match="only 8"):
        StreamExecutionEnvironment().set_mesh(n_devices=9)


def test_compile_cache_placement(monkeypatch, restore_cache_config):
    import jax

    from flink_tpu.utils import platform

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    before = jax.config.jax_compilation_cache_dir
    assert platform.configure_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert platform.configure_compile_cache() == \
        os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == \
        os.path.join(repo, ".jax_cache")


def test_process_cluster_refuses_many_workers_on_a_chip_host(monkeypatch):
    from flink_tpu.cluster import distributed

    monkeypatch.setattr(distributed, "local_tpu_chips", lambda: 1)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    pc = distributed.ProcessCluster("no_such_job:build", n_workers=2)
    with pytest.raises(RuntimeError, match="one process holds all"):
        pc.run(timeout_s=5)
    # host-only workers and a single worker are not refused by the check
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    pc._check_one_process_per_chip()
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    distributed.ProcessCluster(
        "no_such_job:build", n_workers=1)._check_one_process_per_chip()
