"""The keyed pane state's layouts (``ops/pane_layout.py``) and the per-batch
fold through them.

- structural: the traced ``_update_step`` of a single-chip operator touches
  a state-sized array with scatters and nothing else — no reshape, transpose
  or copy of the state (on the chip each of those is a whole-state pass);
- equivalence: leaves and counts after ``_update_step`` equal a numpy fold,
  over kinds, ring sizes, duplicate cells, a ``_PAD_ID`` tail, the last row
  and slot, and batches that arrive right after a key or pane growth;
- the two layouts answer every read and write of the operator alike.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_tpu.core.functions import (CountAggregator, MaxAggregator,
                                      MinAggregator, SumAggregator,
                                      TupleAggregator)
from flink_tpu.operators.window_agg import _PAD_ID, WindowAggOperator
from flink_tpu.ops.pane_layout import KeyGrid, PaneRing
from flink_tpu.windowing import (SlidingEventTimeWindows,
                                 TumblingEventTimeWindows)

K = 4096
KINDS = {"add": SumAggregator, "min": MinAggregator, "max": MaxAggregator}
UFUNC = {"add": np.add, "min": np.minimum, "max": np.maximum}


def sum_op(P, kind="add"):
    return WindowAggOperator(
        TumblingEventTimeWindows.of(100), KINDS[kind](np.float32),
        key_column="key", value_column="v", initial_key_capacity=K,
        initial_panes=P)


def tuple_op(P):
    agg = TupleAggregator({"total": ("v", SumAggregator(np.float32)),
                           "n": ("v", CountAggregator()),
                           "lo": ("v", MinAggregator(np.float32)),
                           "hi": ("v", MaxAggregator(np.float32))})
    return WindowAggOperator(
        SlidingEventTimeWindows.of(P // 2 * 10, 10), agg, key_column="key",
        value_selector=lambda c: c, initial_key_capacity=K)


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
    n_state = K * P
    scatters = 0
    for eqn in _walk(closed.jaxpr):
        name = eqn.primitive.name
        sizes_in = [int(np.prod(v.aval.shape)) for v in eqn.invars
                    if hasattr(v.aval, "shape")]
        sizes_out = [int(np.prod(v.aval.shape)) for v in eqn.outvars]
        if name.startswith("scatter"):
            scatters += 1
            continue
        assert n_state not in sizes_out, f"{name} writes a state-sized array"
        if n_state in sizes_in:
            # the completion token: one element read off the new counts
            assert name == "slice" and sizes_out == [1], \
                f"{name} reads a state-sized array"
    assert scatters == len(op._leaves) + 1


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


def _both(leaf_shape=()):
    """The same random cells in both layouts, and as numpy."""
    rng = np.random.default_rng(7)
    cells = rng.normal(size=(K_, P_) + leaf_shape).astype(np.float32)
    ring = jnp.asarray(np.moveaxis(cells, 1, 0).reshape(
        (P_ * K_,) + leaf_shape))
    return cells, ring, jnp.asarray(cells)


def _as_grid(layout, a):
    return np.asarray(layout.columns(a, jnp.arange(P_, dtype=jnp.int32)))


SLOTS = jnp.asarray([5, 0, P_, 7], jnp.int32)        # one pad
ROWS = jnp.asarray([3, K_ - 1, K_, 0, 17], jnp.int32)  # one pad


@pytest.mark.parametrize("leaf_shape", [(), (3,)], ids=["scalar", "vector"])
@pytest.mark.parametrize("op", [
    "columns", "columns_rows_fill", "set_columns", "fill_columns", "cells",
    "set_cells", "fill_rows", "where_rows", "combine_panes_at", "grow_keys",
    "grow_panes"])
def test_layouts_agree(op, leaf_shape):
    _check_layouts_agree(op, leaf_shape)


def test_layouts_fold_alike():
    _check_layouts_agree("fold", ())


def _check_layouts_agree(op, leaf_shape):
    cells, ring, grid_ = _both(leaf_shape)
    outs = []
    for layout, a in ((RING, ring), (GRID, grid_)):
        rng = np.random.default_rng(11)     # the same draws for both
        if op == "columns":
            out = layout.columns(a, SLOTS[:2])
            want = cells[:, [5, 0]]
        elif op == "columns_rows_fill":
            out = layout.columns(a, SLOTS, rows=16, fill=0)
            want = cells[:16][:, [5, 0, 0, 7]].copy()
            want[:, 2] = 0
        elif op == "set_columns":
            cols = rng.normal(size=(20, 4) + leaf_shape).astype(np.float32)
            out = _as_grid(layout, layout.set_columns(a, SLOTS, cols))
            want = cells.copy()
            want[:20, [5, 0, 7]] = cols[:, [0, 1, 3]]
        elif op == "fill_columns":
            out = _as_grid(layout, layout.fill_columns(a, SLOTS, 9.0))
            want = cells.copy()
            want[:, [5, 0, 7]] = 9.0
        elif op == "cells":
            out = np.asarray(layout.cells(a, ROWS, SLOTS))[[0, 1, 3, 4]]
            out = out[:, [0, 1, 3]]
            want = cells[[3, K_ - 1, 0, 17]][:, [5, 0, 7]]
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
            idx = jnp.asarray([0, 9, K_ - 1, 9], jnp.int32)
            (out,) = layout.combine_panes_at(
                (a,), SLOTS[:2], idx,
                lambda x, y: tuple(jnp.maximum(p, q) for p, q in zip(x, y)))
            want = np.maximum(cells[:, 5], cells[:, 0])[[0, 9, K_ - 1, 9]]
        elif op == "grow_keys":
            big = type(layout)(2 * K_, P_)
            out = np.asarray(big.columns(layout.grow_keys(a, 2 * K_, 4.0),
                                         jnp.arange(P_, dtype=jnp.int32)))
            want = np.full((2 * K_, P_) + leaf_shape, 4.0, np.float32)
            want[:K_] = cells
        elif op == "grow_panes":
            big = type(layout)(K_, 2 * P_)
            src = np.asarray([6, 7, 0], np.int32)     # panes 6, 7, 8
            dst = np.asarray([6, 7, 8], np.int32)
            out = np.asarray(big.columns(
                layout.grow_panes(a, 2 * P_, 4.0, src, dst),
                jnp.arange(2 * P_, dtype=jnp.int32)))
            want = np.full((K_, 2 * P_) + leaf_shape, 4.0, np.float32)
            want[:, dst] = cells[:, src]
        elif op == "fold":
            ids, vals = _batch(rng, K_, P_, n=96, pad=32)
            counts = layout.full(0, (), jnp.int32)
            (leaf,), counts = layout.fold((a,), counts, jnp.asarray(ids),
                                          (jnp.asarray(vals),), ("add",))
            out = np.stack([_as_grid(layout, leaf),
                            _as_grid(layout, counts).astype(np.float32)])
            want_leaf = cells.copy()
            want_counts = np.zeros((K_, P_), np.int32)
            _numpy_fold("add", P_, want_leaf, want_counts, ids, vals)
            want = np.stack([want_leaf, want_counts.astype(np.float32)])
        outs.append(np.asarray(out))
        np.testing.assert_allclose(outs[-1], want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-6, atol=1e-6)


def test_generic_fold_goes_through_the_ring():
    """An aggregate with no scatter kind (sort + segmented scan + set)
    folds into the ring as it does into the grid."""
    cells, ring, grid_ = _both()
    rng = np.random.default_rng(3)
    ids, vals = _batch(rng, K_, P_, n=96, pad=32)
    mul = lambda x, y: tuple(p * q for p, q in zip(x, y))  # noqa: E731
    got = []
    for layout, a in ((RING, ring), (GRID, grid_)):
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
