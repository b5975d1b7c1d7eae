"""The keyed pane state's layouts (``ops/pane_layout.py``) and the per-batch
fold through them.

- structural: the traced ``_update_step`` of a single-chip operator, and the
  traced ``_mesh_update_step`` of the mesh operator, touch a state-sized
  array with scatters and nothing else — no reshape, transpose or copy of
  the state (on the chip each of those is a whole-state pass);
- equivalence: leaves and counts after ``_update_step`` equal a numpy fold,
  over kinds, ring sizes, duplicate cells, a ``_PAD_ID`` tail, the last row
  and slot, and batches that arrive right after a key or pane growth;
- the three layouts answer every read and write of the operator alike;
- the mesh operator's snapshot is the logical grid's, whatever holds it.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_tpu.core.functions import (CountAggregator, MaxAggregator,
                                      MinAggregator, SumAggregator,
                                      TupleAggregator)
from flink_tpu.operators.window_agg import _PAD_ID, WindowAggOperator
from flink_tpu.ops.pane_layout import KeyGrid, PaneRing, ShardRing
from flink_tpu.parallel.mesh import make_mesh, state_sharding
from flink_tpu.parallel.mesh_runtime import MeshWindowAggOperator
from flink_tpu.windowing import (SlidingEventTimeWindows,
                                 TumblingEventTimeWindows)

K = 4096
KINDS = {"add": SumAggregator, "min": MinAggregator, "max": MaxAggregator}
UFUNC = {"add": np.add, "min": np.minimum, "max": np.maximum}


def sum_op(P, kind="add", cls=WindowAggOperator, **kw):
    return cls(
        TumblingEventTimeWindows.of(100), KINDS[kind](np.float32),
        key_column="key", value_column="v", initial_key_capacity=K,
        initial_panes=P, **kw)


def tuple_op(P, cls=WindowAggOperator, **kw):
    agg = TupleAggregator({"total": ("v", SumAggregator(np.float32)),
                           "n": ("v", CountAggregator()),
                           "lo": ("v", MinAggregator(np.float32)),
                           "hi": ("v", MaxAggregator(np.float32))})
    return cls(
        SlidingEventTimeWindows.of(P // 2 * 10, 10), agg, key_column="key",
        value_selector=lambda c: c, initial_key_capacity=K, **kw)


def grid(op, a):
    """A state array of ``op`` as logical ``[K, P]`` numpy."""
    layout = op._layout
    return np.asarray(layout.columns(a, jnp.arange(layout.P, dtype=jnp.int32)))


# ---------------------------------------------------------------- structural
def _walk(jaxpr):
    """Every equation that computes something: a wrapper (the jit of the
    step itself, a nested call) is replaced by the equations of its body;
    a scatter's combiner stays inside the scatter."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("scatter"):
            yield eqn
            continue
        bodies = [getattr(sub, "jaxpr", sub)
                  for v in eqn.params.values()
                  for sub in (v if isinstance(v, (list, tuple)) else (v,))]
        bodies = [b for b in bodies if hasattr(b, "eqns")]
        if not bodies:
            yield eqn
        for body in bodies:
            yield from _walk(body)


def _assert_scatters_only(closed, n_state, n_arrays, reads=()):
    """Every equation of the traced step that takes or gives an array of
    ``n_state`` cells or more is a scatter, ``n_arrays`` of them; ``reads``
    names what else may READ one (primitive -> cells it gives)."""
    scatters = 0
    for eqn in _walk(closed.jaxpr):
        name = eqn.primitive.name
        sizes_in = [int(np.prod(v.aval.shape)) for v in eqn.invars
                    if hasattr(v.aval, "shape")]
        sizes_out = [int(np.prod(v.aval.shape)) for v in eqn.outvars]
        if name.startswith("scatter"):
            # the exchange's bucket scatters fill buffers of the batch's
            # size: the state's are the ones that count
            scatters += max(sizes_in) >= n_state
            continue
        assert max(sizes_out, default=0) < n_state, \
            f"{name} writes a state-sized array"
        if max(sizes_in, default=0) >= n_state:
            assert (name, sizes_out) in reads, \
                f"{name} reads a state-sized array"
    assert scatters == n_arrays


@pytest.mark.parametrize("P", [16, 32])
@pytest.mark.parametrize("make", [sum_op, tuple_op], ids=["sum", "tuple"])
def test_update_step_touches_the_state_with_scatters_only(make, P):
    op = make(P)
    assert (op._K, op._P) == (K, P) and op.kinds is not None
    op._ensure_alloc()
    ids = np.zeros(256, np.int32)
    values = np.ones(256, np.float32)
    if make is tuple_op:
        values = {"v": values}
    closed = jax.make_jaxpr(WindowAggOperator._update_step,
                            static_argnums=(0, 1))(
        op, op._layout, op._leaves, op._counts, ids, values)
    # the completion token: one element read off the new counts
    _assert_scatters_only(closed, K * P, len(op._leaves) + 1,
                          reads=[("slice", [1])])


@pytest.mark.parametrize("P", [16, 32])
@pytest.mark.parametrize("make", [sum_op, tuple_op], ids=["sum", "tuple"])
def test_mesh_update_step_touches_the_state_with_scatters_only(make, P):
    """The sharded step on a mesh of four: inside the ``shard_map`` a
    device's block (``K / 4 x P`` cells) meets scatters and nothing else,
    and outside it nothing touches the whole arrays: no ``reshape``,
    ``copy``, ``transpose``, ``concatenate`` or ``gather`` of a state
    array anywhere in the step."""
    op = make(P, cls=MeshWindowAggOperator, mesh=make_mesh(4))
    assert (op._K, op._P) == (K, P) and op.kinds is not None
    op._ensure_alloc()
    ids = np.arange(256, dtype=np.int32) * P
    values = np.ones(256, np.float32)
    if make is tuple_op:
        values = {"v": values}
    batch, cap, cols, _packed = op._route_batch(ids, values)
    assert len(batch) == 1                # the whole batch is one array
    closed = jax.make_jaxpr(MeshWindowAggOperator._mesh_update_step,
                            static_argnums=(0, 1, 4, 5))(
        op, op._layout, (op._leaves, op._counts), batch, cap, cols)
    assert 4 * cap * 4 < K // 4 * P       # no exchange buffer is state-sized
    # the completion token: one cell a device read off the new counts
    _assert_scatters_only(closed, K // 4 * P, len(op._leaves) + 1,
                          reads=[("slice", [1])])


# --------------------------------------------------------------- equivalence
def _numpy_fold(kind, P, leaf, counts, ids, vals):
    live = ids != _PAD_ID
    rows, slots = ids[live] // P, ids[live] % P
    UFUNC[kind].at(leaf, (rows, slots), vals[live])
    np.add.at(counts, (rows, slots), 1)


def _batch(rng, K_, P, n=192, pad=64):
    """Uniform cells with duplicates inside the batch, the last row and
    the last slot among them, and a ``_PAD_ID`` tail."""
    rows = rng.integers(0, K_, n)
    slots = rng.integers(0, P, n)
    rows[:8] = rows[8:16]                   # duplicate cells
    slots[:8] = slots[8:16]
    rows[16:20] = K_ - 1                    # the last row ...
    slots[18:24] = P - 1                    # ... and the last slot
    ids = np.full(n + pad, _PAD_ID, np.int32)
    ids[:n] = rows * P + slots
    vals = rng.normal(size=n + pad).astype(np.float32)
    return ids, vals


@pytest.mark.parametrize("after", ["alloc", "grow_keys", "grow_panes"])
@pytest.mark.parametrize("P", [16, 32])
@pytest.mark.parametrize("kind", ["add", "min", "max"])
def test_update_step_equals_a_numpy_fold(kind, P, after):
    rng = np.random.default_rng(hash((kind, P, after)) % 2**32)
    op = sum_op(P, kind)
    op._ensure_alloc()
    init = np.asarray(op.spec.leaf_inits[0], np.float32)
    leaf = np.full((K, P), init, np.float32)
    counts = np.zeros((K, P), np.int32)

    def step():
        ids, vals = _batch(rng, op._K, op._P)
        _numpy_fold(kind, op._P, leaf, counts, ids, vals)
        (op._leaves, op._counts, token) = op._update_step(
            op._layout, op._leaves, op._counts, ids, vals)
        assert int(token) == counts[0, 0]

    step()
    if after == "grow_keys":
        op._grow_keys(K + 1)
        assert op._K == 2 * K
        grown = np.full((2 * K, P), init, np.float32)
        grown[:K] = leaf
        leaf = grown
        counts = np.concatenate([counts, np.zeros((K, P), np.int32)])
    elif after == "grow_panes":
        # live panes 5 .. P+4 keep their cells: slot p % P -> p % 2P
        op.pane_base, op.max_pane = 5, P + 4
        op._grow_panes(2 * P)
        assert op._P == 2 * P
        panes = np.arange(5, P + 5)
        grown = np.full((K, 2 * P), init, np.float32)
        grown[:, panes % (2 * P)] = leaf[:, panes % P]
        grown_counts = np.zeros((K, 2 * P), np.int32)
        grown_counts[:, panes % (2 * P)] = counts[:, panes % P]
        leaf, counts = grown, grown_counts
    step()
    step()
    assert np.array_equal(grid(op, op._counts), counts)
    got = grid(op, op._leaves[0])
    if kind == "add":
        np.testing.assert_allclose(got, leaf, rtol=1e-6, atol=1e-6)
    else:
        assert np.array_equal(got, leaf)


def test_ring_size_must_be_a_power_of_two():
    with pytest.raises(ValueError, match="power of two"):
        PaneRing(K, 12)


# ------------------------------------------------- the layouts answer alike
P_ = 8
K_ = 64
RING, GRID = PaneRing(K_, P_), KeyGrid(K_, P_)


def _shard_ring(D):
    return ShardRing(K_, P_, make_mesh(D))


def _held(layout, cells):
    """Logical ``[K, P, *leaf]`` numpy cells as ``layout`` holds them."""
    K, P, leaf = cells.shape[0], cells.shape[1], cells.shape[2:]
    if isinstance(layout, KeyGrid):
        return jnp.asarray(cells)
    if isinstance(layout, PaneRing):
        return jnp.asarray(np.moveaxis(cells, 1, 0).reshape((P * K,) + leaf))
    D = layout.D                # a device's rows, pane-major, D blocks on end
    blocks = np.moveaxis(cells.reshape((D, K // D, P) + leaf), 2, 1)
    return jax.device_put(blocks.reshape((P * K,) + leaf),
                          state_sharding(layout.mesh))


def _is_arrays(x):
    if isinstance(x, tuple):
        return bool(x) and all(_is_arrays(y) for y in x)
    return isinstance(x, (np.ndarray, jax.Array))


class _Programs:
    """A :class:`ShardRing` whose every method call is one jitted program
    over its array arguments, as the operator's steps run them (called
    eagerly a ``shard_map`` runs operation by operation: seconds a call)."""

    def __init__(self, layout):
        self.layout = layout

    def __getattr__(self, name):
        attr = getattr(self.layout, name)
        if not callable(attr):
            return attr

        def call(*args, **kw):
            dyn = [i for i, x in enumerate(args) if _is_arrays(x)]

            def run(*arrays):
                full = list(args)
                for i, x in zip(dyn, arrays):
                    full[i] = x
                return attr(*full, **kw)

            return jax.jit(run)(*(args[i] for i in dyn))

        return call


def _calls(layout):
    return _Programs(layout) if isinstance(layout, ShardRing) else layout


def _both(leaf_shape=(), layouts=(RING, GRID)):
    """The same random cells as numpy and in every layout."""
    rng = np.random.default_rng(7)
    cells = rng.normal(size=(K_, P_) + leaf_shape).astype(np.float32)
    return (cells, *(_held(layout, cells) for layout in layouts))


def _as_grid(layout, a):
    return np.asarray(layout.columns(a, jnp.arange(layout.P,
                                                   dtype=jnp.int32)))


SLOTS = jnp.asarray([5, 0, P_, 7], jnp.int32)        # one pad
ROWS = jnp.asarray([3, K_ - 1, K_, 0, 17], jnp.int32)  # one pad
#: every method of the interface; rows 15, 16 and 17 straddle a device's
#: block at D = 4 (16 rows each) and at D = 8 (8 each); ``rows=5`` lies
#: inside the first block, 16 is one block (two at D = 8), 20 crosses one
OPS = ["columns", "columns_rows_fill", "columns_rows_inside",
       "columns_rows_across", "set_columns", "set_columns_inside",
       "set_columns_all", "fill_columns", "cells", "cells_straddle",
       "set_cells", "fill_rows", "where_rows", "combine_panes_at",
       "grow_keys", "grow_panes"]
FOLDS = ["fold", "fold_after_grow_keys", "fold_after_grow_panes"]


@pytest.mark.parametrize("leaf_shape", [(), (3,)], ids=["scalar", "vector"])
@pytest.mark.parametrize("op", OPS)
def test_layouts_agree(op, leaf_shape):
    _check_layouts_agree(op, leaf_shape, (RING, GRID))


@pytest.mark.parametrize("op", FOLDS)
def test_layouts_fold_alike(op):
    _check_layouts_agree(op, (), (RING, GRID))


@pytest.mark.parametrize("D", [4, 8])
@pytest.mark.parametrize("leaf_shape", [(), (3,)], ids=["scalar", "vector"])
@pytest.mark.parametrize("op", OPS)
def test_shard_ring_agrees_with_both(op, leaf_shape, D):
    _check_layouts_agree(op, leaf_shape, (RING, GRID, _shard_ring(D)))


@pytest.mark.parametrize("D", [4, 8])
@pytest.mark.parametrize("op", FOLDS)
def test_shard_ring_folds_like_both(op, D):
    _check_layouts_agree(op, (), (RING, GRID, _shard_ring(D)))


def test_shard_ring_cell_index_is_the_documented_one():
    """Cell ``(row, slot)`` of ``ShardRing(K, P, mesh)`` sits at
    ``(row // Ks) * P * Ks + slot * Ks + row % Ks``, device ``d`` holding
    the flat range ``[d * P * Ks, (d + 1) * P * Ks)``."""
    layout = _shard_ring(4)
    ks = K_ // 4
    cells = np.arange(K_ * P_, dtype=np.float32).reshape(K_, P_)
    a = _held(layout, cells)
    flat = np.asarray(a)
    for row, slot in ((0, 0), (15, 7), (16, 0), (17, 3), (K_ - 1, P_ - 1)):
        at = (row // ks) * P_ * ks + slot * ks + row % ks
        assert flat[at] == cells[row, slot]
    for d, shard in enumerate(sorted(a.addressable_shards,
                                     key=lambda s: s.index[0].start)):
        block = np.asarray(shard.data)
        assert block.shape == (P_ * ks,)
        assert np.array_equal(block.reshape(P_, ks).T,
                              cells[d * ks:(d + 1) * ks])
    assert layout.local == PaneRing(ks, P_)
    with pytest.raises(ValueError, match="split"):
        ShardRing(K_ + 2, P_, make_mesh(4))


def _check_layouts_agree(op, leaf_shape, layouts):
    cells, *held = _both(leaf_shape, layouts)
    outs = []
    for held_as, a in zip(layouts, held):
        layout = _calls(held_as)
        rng = np.random.default_rng(11)     # the same draws for all
        if op == "columns":
            out = layout.columns(a, SLOTS[:2])
            want = cells[:, [5, 0]]
        elif op == "columns_rows_fill":
            out = layout.columns(a, SLOTS, rows=16, fill=0)
            want = cells[:16][:, [5, 0, 0, 7]].copy()
            want[:, 2] = 0
        elif op in ("columns_rows_inside", "columns_rows_across"):
            n = 5 if op.endswith("inside") else 20
            out = layout.columns(a, SLOTS[:2], rows=n)
            want = cells[:n][:, [5, 0]]
        elif op in ("set_columns", "set_columns_inside", "set_columns_all"):
            n = {"set_columns": 20, "set_columns_inside": 5,
                 "set_columns_all": K_}[op]
            cols = rng.normal(size=(n, 4) + leaf_shape).astype(np.float32)
            out = _as_grid(layout, layout.set_columns(a, SLOTS, cols))
            want = cells.copy()
            want[:n, [5, 0, 7]] = cols[:, [0, 1, 3]]
        elif op == "fill_columns":
            out = _as_grid(layout, layout.fill_columns(a, SLOTS, 9.0))
            want = cells.copy()
            want[:, [5, 0, 7]] = 9.0
        elif op == "cells":
            out = np.asarray(layout.cells(a, ROWS, SLOTS))[[0, 1, 3, 4]]
            out = out[:, [0, 1, 3]]
            want = cells[[3, K_ - 1, 0, 17]][:, [5, 0, 7]]
        elif op == "cells_straddle":
            rows = [15, 16, 17, 7, 8, 47, 48]
            out = layout.cells(a, jnp.asarray(rows, jnp.int32),
                               jnp.arange(P_, dtype=jnp.int32))
            want = cells[rows]
        elif op == "set_cells":
            vals = rng.normal(size=(5, 4) + leaf_shape).astype(np.float32)
            out = _as_grid(layout, layout.set_cells(a, ROWS, SLOTS, vals))
            want = cells.copy()
            want[np.ix_([3, K_ - 1, 0, 17], [5, 0, 7])] = \
                vals[[0, 1, 3, 4]][:, [0, 1, 3]]
        elif op == "fill_rows":
            out = _as_grid(layout, layout.fill_rows(a, ROWS, -1.0))
            want = cells.copy()
            want[[3, K_ - 1, 0, 17]] = -1.0
        elif op == "where_rows":
            mask = rng.random(K_) < 0.3
            out = _as_grid(layout, layout.where_rows(a, jnp.asarray(mask),
                                                     0.0))
            want = cells.copy()
            want[mask] = 0.0
        elif op == "combine_panes_at":
            idx = jnp.asarray([0, 9, K_ - 1, 9, 16, 15], jnp.int32)
            (out,) = layout.combine_panes_at(
                (a,), SLOTS[:2], idx,
                lambda x, y: tuple(jnp.maximum(p, q) for p, q in zip(x, y)))
            want = np.maximum(cells[:, 5],
                              cells[:, 0])[[0, 9, K_ - 1, 9, 16, 15]]
        elif op == "grow_keys":
            big = _calls(dataclasses.replace(held_as, K=2 * K_))
            out = _as_grid(big, layout.grow_keys(a, 2 * K_, 4.0))
            want = np.full((2 * K_, P_) + leaf_shape, 4.0, np.float32)
            want[:K_] = cells
        elif op == "grow_panes":
            big = _calls(dataclasses.replace(held_as, P=2 * P_))
            src = np.asarray([6, 7, 0], np.int32)     # panes 6, 7, 8
            dst = np.asarray([6, 7, 8], np.int32)
            out = _as_grid(big, layout.grow_panes(a, 2 * P_, 4.0, src, dst))
            want = np.full((K_, 2 * P_) + leaf_shape, 4.0, np.float32)
            want[:, dst] = cells[:, src]
        elif op in FOLDS:
            want_leaf = cells.copy()
            want_counts = np.zeros((K_, P_), np.int32)
            counts = layout.full(0, (), jnp.int32)
            if isinstance(held_as, ShardRing):
                counts = jax.device_put(counts, state_sharding(held_as.mesh))
            if op == "fold_after_grow_keys":
                a = layout.grow_keys(a, 2 * K_, 0.0)
                counts = layout.grow_keys(counts, 2 * K_, 0)
                layout = _calls(dataclasses.replace(held_as, K=2 * K_))
                want_leaf = np.concatenate(
                    [want_leaf, np.zeros((K_, P_), np.float32)])
                want_counts = np.zeros((2 * K_, P_), np.int32)
            elif op == "fold_after_grow_panes":
                same = np.arange(P_, dtype=np.int32)
                a = layout.grow_panes(a, 2 * P_, 0.0, same, same)
                counts = layout.grow_panes(counts, 2 * P_, 0, same, same)
                layout = _calls(dataclasses.replace(held_as, P=2 * P_))
                want_leaf = np.concatenate(
                    [want_leaf, np.zeros((K_, P_), np.float32)], axis=1)
                want_counts = np.zeros((K_, 2 * P_), np.int32)
            ids, vals = _batch(rng, layout.K, layout.P, n=96, pad=32)
            (leaf,), counts = layout.fold((a,), counts, jnp.asarray(ids),
                                          (jnp.asarray(vals),), ("add",))
            out = np.stack([_as_grid(layout, leaf),
                            _as_grid(layout, counts).astype(np.float32)])
            _numpy_fold("add", layout.P, want_leaf, want_counts, ids, vals)
            want = np.stack([want_leaf, want_counts.astype(np.float32)])
        outs.append(np.asarray(out))
        np.testing.assert_allclose(outs[-1], want, rtol=1e-6, atol=1e-6)
    for other in outs[1:]:
        np.testing.assert_allclose(outs[0], other, rtol=1e-6, atol=1e-6)


def test_generic_fold_goes_through_the_ring():
    """An aggregate with no scatter kind (sort + segmented scan + set)
    folds into the ring, and into a ring per device, as into the grid."""
    layouts = (RING, GRID, _shard_ring(4))
    cells, *held = _both((), layouts)
    rng = np.random.default_rng(3)
    ids, vals = _batch(rng, K_, P_, n=96, pad=32)
    mul = lambda x, y: tuple(p * q for p, q in zip(x, y))  # noqa: E731
    got = []
    for layout, a in zip(map(_calls, layouts), held):
        (leaf,), counts = layout.fold(
            (a,), layout.full(0, (), jnp.int32), jnp.asarray(ids),
            (jnp.asarray(vals),), None, mul)
        got.append((_as_grid(layout, leaf), _as_grid(layout, counts)))
    want, want_counts = cells.copy(), np.zeros((K_, P_), np.int32)
    live = ids != _PAD_ID
    np.multiply.at(want, (ids[live] // P_, ids[live] % P_), vals[live])
    np.add.at(want_counts, (ids[live] // P_, ids[live] % P_), 1)
    for leaf, counts in got:
        np.testing.assert_allclose(leaf, want, rtol=1e-5, atol=1e-6)
        assert np.array_equal(counts, want_counts)


# ------------------------------------- programs that do not follow live panes
def test_snapshot_and_clear_compile_once_for_any_number_of_live_panes():
    """A cut's reads and an expiry's clears are shaped by the state, not by
    how many panes are live or expire at once: that count follows the
    job's pace, and a program keyed on it compiles in the middle of a run."""
    from flink_tpu.operators import window_agg
    from flink_tpu.testing import KeyedOneInputOperatorHarness

    op = WindowAggOperator(
        TumblingEventTimeWindows.of(100), SumAggregator(np.float32),
        key_column="key", value_column="v", emit_tier="device",
        initial_key_capacity=K)
    h = KeyedOneInputOperatorHarness(op)
    sizes, snaps = [], []
    for live in (1, 2, 3):
        h.process_elements([{"key": k, "v": np.float32(live)}
                            for k in range(5)], [100 * (live - 1) + 7] * 5)
        snaps.append(op.snapshot_state())
        sizes.append((window_agg._snapshot_read_step._cache_size(),
                      WindowAggOperator._clear_panes_step._cache_size()))
    assert [s["counts"].shape for s in snaps] == [(5, 1), (5, 2), (5, 3)]
    assert np.array_equal(snaps[2]["leaves"][0],
                          np.tile(np.float32([1, 2, 3]), (5, 1)))
    h.process_watermark(99)         # one pane expires, then two at once
    one = WindowAggOperator._clear_panes_step._cache_size()
    h.process_watermark(299)
    assert WindowAggOperator._clear_panes_step._cache_size() == one
    # leaf and counts: two read programs, from the first cut on
    assert sizes[0][0] == sizes[1][0] == sizes[2][0]
    assert len(h.extract_output_rows()) == 15


# ------------------------------------- the mesh snapshot is the logical grid's
SNAP_KEYS, SNAP_B = 617, 512    # rows: shards 0, 1 full, 2 part, 3 none


def _snap_op(D, emit_tier="device"):
    """The one-chip operator (``D`` None) or the mesh one on ``D`` devices."""
    from flink_tpu.core.functions import RuntimeContext
    kw = dict(key_column="key", value_column="v", emit_tier=emit_tier,
              snapshot_source="device" if emit_tier == "device" else "mirror",
              initial_key_capacity=1024)
    assigner = TumblingEventTimeWindows.of(100)
    agg = SumAggregator(np.float32)
    op = (WindowAggOperator(assigner, agg, **kw) if D is None else
          MeshWindowAggOperator(assigner, agg, mesh=make_mesh(D), **kw))
    op.open(RuntimeContext())
    return op


def _snap_batches(seed, first_pane, n=3):
    """One batch a pane; whole-number values, so an f32 sum is exact in
    any order and the fixture can be byte for byte."""
    from flink_tpu.core.batch import RecordBatch
    rng = np.random.default_rng(seed)
    keys = np.concatenate([np.arange(SNAP_KEYS),        # arrival order = slot
                           rng.integers(0, SNAP_KEYS, n * SNAP_B)])
    keys = keys[:n * SNAP_B].reshape(n, SNAP_B).astype(np.int64)
    vals = rng.integers(0, 8, (n, SNAP_B)).astype(np.float32)
    return [(RecordBatch({"key": keys[i], "v": vals[i]},
                         timestamps=np.full(SNAP_B, (first_pane + i) * 100 + 5,
                                            np.int64)), keys[i], vals[i])
            for i in range(n)]


def _fired(out):
    return [(int(np.asarray(b.column("window_start"))[0]), len(b),
             np.asarray(b.column("key")).tobytes(),
             np.asarray(b.column("result")).tobytes())
            for b in out if hasattr(b, "columns") and "result" in b.columns]


def _same_bytes(a, b, path="snapshot"):
    """Two snapshot values are one another's, key for key, byte for byte."""
    assert type(a) is type(b), path
    if isinstance(a, dict):
        assert list(a) == list(b), path
        for k in a:
            _same_bytes(a[k], b[k], f"{path}[{k!r}]")
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b), path
        for i, (x, y) in enumerate(zip(a, b)):
            _same_bytes(x, y, f"{path}[{i}]")
    elif isinstance(a, np.ndarray):
        assert (a.dtype, a.shape) == (b.dtype, b.shape), path
        assert a.tobytes() == b.tobytes(), path
    else:
        assert a == b, path


def test_mesh_snapshot_is_the_logical_grids_and_restores_at_any_size():
    """What a mesh job writes does not know how a device holds its block:
    the snapshot equals ``split_to_shard_slices`` of the dense columns of
    the logical ``[K, P]`` grid (built here with numpy, and equal to the
    one-chip operator's own), and it restores onto four devices, two, and
    one chip's ``PaneRing`` with equal fires."""
    from flink_tpu.core.batch import Watermark
    from flink_tpu.state.shard_layout import (ShardLayout,
                                              split_to_shard_slices)

    ops = {D: _snap_op(D) for D in (None, 4)}
    counts = np.zeros((SNAP_KEYS, 3), np.int32)
    sums = np.zeros((SNAP_KEYS, 3), np.float32)
    for pane, (batch, keys, vals) in enumerate(_snap_batches(1, 0)):
        np.add.at(counts[:, pane], keys, 1)     # a key's slot is its value
        np.add.at(sums[:, pane], keys, vals)
        for op in ops.values():
            op.process_batch(batch)
    snaps = {}
    for D, op in ops.items():
        op.prepare_snapshot_pre_barrier()
        snaps[D] = op.snapshot_state()
    mesh_op = ops[4]
    assert isinstance(mesh_op._layout, ShardRing)
    assert isinstance(ops[None]._layout, PaneRing)
    dense = dict(snaps[None])
    assert np.array_equal(dense["counts"], counts)
    assert np.array_equal(dense["leaves"][0], sums)
    dense["counts"], dense["leaves"] = counts, [sums]
    fixture = split_to_shard_slices(dense, ShardLayout(4, mesh_op._K), 128)
    assert [s["row_range"] for s in fixture["shard_slices"]] == \
        [(0, 256), (256, 512), (512, 617), (617, 617)]
    assert set(snaps[4]) == set(fixture)
    for k in fixture:
        _same_bytes(snaps[4][k], fixture[k], f"snapshot[{k!r}]")

    tails = {}
    for D in (4, 2, None):
        op = _snap_op(D)
        op.restore_state(snaps[4])
        out = []
        for batch, _k, _v in _snap_batches(2, 2):
            out += op.process_batch(batch)
        out += op.process_watermark(Watermark(10_000))
        tails[D] = _fired(out)
    assert len(tails[4]) == 5 and tails[4] == tails[2] == tails[None]
    # ... and the reverse: the one-chip snapshot onto the mesh
    op = _snap_op(4)
    op.restore_state(snaps[None])
    out = []
    for batch, _k, _v in _snap_batches(2, 2):
        out += op.process_batch(batch)
    assert _fired(out + op.process_watermark(Watermark(10_000))) == tails[4]


def test_mesh_step_compiles_once_across_a_restore_and_a_refresh():
    """State comes back from a restore (``set_columns`` per array) and from
    ``device_refresh`` (``_refresh_step``) placed as the step left it:
    one batch geometry is one compiled ``_mesh_update_step``."""
    writer = _snap_op(4, "host")
    for batch, _k, _v in _snap_batches(1, 0):
        writer.process_batch(batch)
    writer.prepare_snapshot_pre_barrier()
    snap = writer.snapshot_state()

    op = _snap_op(4, "host")
    if op.mesh_step_cache_size() < 0:
        pytest.skip("jax build without the jit cache probe")
    op.restore_state(snap)
    before = op.mesh_step_cache_size()     # the cache is the class's
    batches = [b for b, _k, _v in _snap_batches(2, 2, n=4)]
    op.process_batch(batches[0])
    op.process_batch(batches[1])
    op.flush_pipeline()
    assert op.mesh_step_cache_size() == before + 1
    op._device_stale = True                # as deferred sync leaves it
    op.device_refresh()
    assert not op._device_stale
    for a in (*op._leaves, op._counts):
        assert a.sharding.is_equivalent_to(op.sharding, a.ndim)
    assert op.verify_mirror()
    op.process_batch(batches[2])
    op.process_batch(batches[3])
    op.flush_pipeline()
    assert op.mesh_step_cache_size() == before + 1
