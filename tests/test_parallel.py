"""Sharded execution tests on the 8-device virtual CPU mesh (conftest forces
``--xla_force_host_platform_device_count=8`` — the MiniCluster analog)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_tpu.core.batch import RecordBatch
from flink_tpu.core.functions import SumAggregator
from flink_tpu.parallel.exchange import (bucket_plan, bucket_rows,
                                         make_all_to_all_exchange)
from flink_tpu.parallel.mesh import KeyGroupSharding, make_mesh, state_sharding
from flink_tpu.parallel.window_shard import sharded_window_operator
from flink_tpu.testing.harness import KeyedOneInputOperatorHarness
from flink_tpu.windowing import TumblingEventTimeWindows


def test_mesh_and_sharding_specs():
    mesh = make_mesh(8)
    assert mesh.devices.size == 8
    sh = KeyGroupSharding(max_parallelism=128, num_shards=8)
    kg = np.arange(128)
    shards = sh.shard_of_key_group(kg)
    # contiguous ranges, all shards used, monotone
    assert shards.min() == 0 and shards.max() == 7
    assert (np.diff(shards) >= 0).all()
    counts = np.bincount(shards, minlength=8)
    assert counts.min() >= 128 // 8 - 1


def test_sharded_window_agg_matches_single_device():
    rng = np.random.default_rng(0)
    n = 5000
    keys = rng.integers(0, 257, n)
    vals = rng.random(n).astype(np.float32)
    ts = np.sort(rng.integers(0, 5000, n))

    def run(op):
        h = KeyedOneInputOperatorHarness(op)
        for lo in range(0, n, 512):
            hi = min(lo + 512, n)
            h.process_batch(RecordBatch({"k": keys[lo:hi], "v": vals[lo:hi]},
                                        timestamps=ts[lo:hi]))
        h.process_watermark(10_000)
        return {(r["k"], r["window_start"]): r["result"]
                for r in h.extract_output_rows()}

    from flink_tpu.operators.window_agg import WindowAggOperator
    single = run(WindowAggOperator(TumblingEventTimeWindows.of(1000),
                                   SumAggregator(jnp.float32),
                                   key_column="k", value_column="v"))
    mesh = make_mesh(8)
    sharded = run(sharded_window_operator(
        mesh, assigner=TumblingEventTimeWindows.of(1000),
        agg=SumAggregator(jnp.float32), key_column="k", value_column="v"))
    assert set(single) == set(sharded)
    for kk in single:
        assert abs(single[kk] - sharded[kk]) < 1e-3


def test_sharded_state_is_actually_distributed():
    mesh = make_mesh(8)
    op = sharded_window_operator(
        mesh, assigner=TumblingEventTimeWindows.of(100),
        agg=SumAggregator(jnp.float32), key_column="k", value_column="v")
    h = KeyedOneInputOperatorHarness(op)
    h.process_batch(RecordBatch({"k": np.arange(100), "v": np.ones(100, np.float32)},
                                timestamps=np.zeros(100, np.int64)))
    leaf = op._leaves[0]
    assert len(leaf.sharding.device_set) == 8


def test_all_to_all_exchange_routes_by_shard():
    mesh = make_mesh(8)
    D, B, cap = 8, 16, 32
    ex = make_all_to_all_exchange(mesh, num_leaves=2, cap=cap)
    rng = np.random.default_rng(3)
    # [D*B] records scattered over devices; dest = key % D
    keys = rng.integers(0, 1000, D * B).astype(np.int32)
    vals = rng.random(D * B).astype(np.float32)
    dest = (keys % D).astype(np.int32)
    rx_leaves, rx_valid, overflow = ex(jnp.asarray(dest),
                                       jnp.asarray(keys), jnp.asarray(vals))
    assert int(np.sum(np.asarray(overflow))) == 0
    rx_keys = np.asarray(rx_leaves[0])
    rx_vals = np.asarray(rx_leaves[1])
    valid = np.asarray(rx_valid)
    # every record arrives exactly once, on the device owning its key
    assert valid.sum() == D * B
    got = sorted(zip(rx_keys[valid].tolist(), rx_vals[valid].tolist()))
    want = sorted(zip(keys.tolist(), vals.tolist()))
    assert got == want
    # placement: received row i on shard s must satisfy key % D == s
    per_dev = valid.reshape(D, D * cap)
    keys_dev = rx_keys.reshape(D, D * cap)
    for s in range(D):
        assert (keys_dev[s][per_dev[s]] % D == s).all()


def test_exchange_overflow_reported():
    mesh = make_mesh(8)
    cap = 2
    ex = make_all_to_all_exchange(mesh, num_leaves=1, cap=cap)
    # all records on every device target shard 0 -> overflow beyond cap
    dest = jnp.zeros(8 * 20, jnp.int32)
    vals = jnp.arange(8 * 20, dtype=jnp.float32)
    _, rx_valid, overflow = ex(dest, vals)
    assert int(np.asarray(overflow).sum()) == 8 * 20 - 8 * cap
    assert int(np.asarray(rx_valid).sum()) == 8 * cap


def test_resizing_exchange_forced_overflow_zero_loss():
    """VERDICT r1 #2: overflow must block/resend, never drop.  Every record
    lands on every device targeting ONE shard at a tiny initial capacity;
    the resizing exchange must deliver all of them exactly once."""
    from flink_tpu.parallel.exchange import ResizingExchange

    mesh = make_mesh(8)
    D, B = 8, 20
    ex = ResizingExchange(mesh, num_leaves=1, cap=2)
    dest = jnp.zeros(D * B, jnp.int32)          # extreme skew: all -> shard 0
    vals = jnp.arange(D * B, dtype=jnp.float32)
    rx_leaves, rx_valid, cap_used = ex(dest, vals)
    valid = np.asarray(rx_valid)
    got = sorted(np.asarray(rx_leaves[0])[valid].tolist())
    assert got == sorted(np.asarray(vals).tolist())   # zero loss, no dupes
    assert cap_used >= B                              # capacity renegotiated
    # steady state at the grown capacity: next call needs no further resize
    rx2, rv2, cap2 = ex(dest, vals)
    assert cap2 == cap_used
    assert int(np.asarray(rv2).sum()) == D * B


def test_resizing_exchange_max_cap_guard():
    from flink_tpu.parallel.exchange import ResizingExchange

    mesh = make_mesh(8)
    ex = ResizingExchange(mesh, num_leaves=1, cap=2, max_cap=4)
    dest = jnp.zeros(8 * 20, jnp.int32)
    vals = jnp.ones(8 * 20, jnp.float32)
    with pytest.raises(RuntimeError, match="overflow at max capacity"):
        ex(dest, vals)


# ---------------------------------------------------------------------------
# the bucket plan (ISSUE 33): row i lands in cell dest[i] * cap + r, r the
# number of EARLIER rows bound for the same destination; no loop in the
# lowered program
# ---------------------------------------------------------------------------

def _plan_reference(dest, D, cap):
    """The placement rule as a Python loop over rows."""
    seen = [0] * D
    flat = np.empty(len(dest), np.int64)
    for i, d in enumerate(dest.tolist()):
        flat[i] = d * cap + seen[d] if seen[d] < cap else D * cap
        seen[d] += 1
    return flat


@pytest.mark.parametrize("skew", ["uniform", "one_dest"])
@pytest.mark.parametrize("cap_of", ["ceiling", "below"])
@pytest.mark.parametrize("B", [64, 16384])
@pytest.mark.parametrize("D", [2, 4, 8])
def test_bucket_plan_is_the_placement_rule(D, B, cap_of, skew):
    """``cap`` at the ceiling (a block's length: nothing can overflow) and
    below it (the sentinel, ``valid_src`` and the overflow count)."""
    rng = np.random.default_rng(D * 100003 + B)
    dest = (np.full(B, D - 1, np.int32) if skew == "one_dest"
            else rng.integers(0, D, B).astype(np.int32))
    cap = B if cap_of == "ceiling" else max(1, B // (D + 1))
    flat, valid = jax.jit(bucket_plan, static_argnums=(1, 2))(
        jnp.asarray(dest), D, cap)
    want = _plan_reference(dest, D, cap)
    assert np.array_equal(np.asarray(flat), want)
    assert np.array_equal(np.asarray(valid), want < D * cap)
    overflow = int(np.sum(~np.asarray(valid)))
    assert overflow == sum(max(0, int(n) - cap)
                           for n in np.bincount(dest, minlength=D))
    assert (overflow == 0) == (cap_of == "ceiling")
    # the buckets: rows of one destination in batch order, the rest fill
    rows = np.arange(B, dtype=np.int32)
    got = np.asarray(bucket_rows(jnp.asarray(rows), flat, D, cap, -1))
    for d in range(D):
        mine = rows[dest == d][:cap]
        assert np.array_equal(got[d, :len(mine)], mine)
        assert (got[d, len(mine):] == -1).all()


@pytest.mark.parametrize("D", [2, 4, 8])
def test_bucket_plan_lowers_to_no_loop(D):
    """The sort-and-search plan lowered to a ``while`` (``searchsorted``'s
    binary search: 15 rounds at a block of 16,384); the count has none,
    and no sort either."""
    text = jax.jit(bucket_plan, static_argnums=(1, 2)).lower(
        jax.ShapeDtypeStruct((16384,), jnp.int32), D, 16384).as_text()
    assert "while" not in text
    assert "sort" not in text


def test_exchange_keeps_batch_order_within_a_destination():
    """Through the collective: what shard ``s`` receives from source ``d``
    is ``d``'s rows bound for ``s``, in ``d``'s batch order."""
    mesh = make_mesh(4)
    D, B, cap = 4, 64, 64
    rng = np.random.default_rng(33)
    dest = rng.integers(0, D, D * B).astype(np.int32)
    rows = np.arange(D * B, dtype=np.int32)
    (rx,), rx_valid, overflow = make_all_to_all_exchange(
        mesh, num_leaves=1, cap=cap)(jnp.asarray(dest), jnp.asarray(rows))
    assert int(np.asarray(overflow).sum()) == 0
    rx = np.asarray(rx).reshape(D, D, cap)          # [shard, source, cell]
    valid = np.asarray(rx_valid).reshape(D, D, cap)
    for s in range(D):
        for d in range(D):
            block = slice(d * B, (d + 1) * B)
            want = rows[block][dest[block] == s]
            assert np.array_equal(rx[s, d][valid[s, d]], want)
            assert valid[s, d, :len(want)].all()
