"""The keyed exchange: what a hash edge does per record on the host.

Two ends of one mechanism: ``OutputDispatcher._emit_hash`` on the producer
(key groups, one index pass, one gather per column and target) and
``KeyByOperator`` at the head of the consumer's chain (no hashing for a
batch that carries key groups of its own key; none at all where nobody
reads them).  The split is held against the old masked split, written out
here as the plain reference; the "at most once" half against the
counters of the dispatcher and of the operator, which
``Task.key_group_records`` sums.
"""

import pickle

import numpy as np
import pytest

from flink_tpu.cluster.channels import LocalChannel, OutputDispatcher
from flink_tpu.core import keygroups
from flink_tpu.core.batch import RecordBatch
from flink_tpu.native import codec
from flink_tpu.operators.basic import KeyByOperator, MapOperator
from flink_tpu.operators.process import KeyedProcessFunction

ROWS = 1000


def _keys(kind: str, rows: int = ROWS, seed: int = 7) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if kind == "int64":
        return rng.integers(-2 ** 62, 2 ** 62, rows).astype(np.int64)
    if kind == "int32":
        return rng.integers(-2 ** 31, 2 ** 31, rows).astype(np.int32)
    if kind == "string":
        return np.asarray([f"user-{i}" for i in rng.integers(0, 300, rows)],
                          object)
    assert kind == "composite"     # dataset/optimizer.py's packed void keys
    fields = np.dtype([("f0", ">i8"), ("f1", ">i8")])
    arr = np.empty(rows, fields)
    arr["f0"] = rng.integers(0, 50, rows)
    arr["f1"] = rng.integers(-10 ** 9, 10 ** 9, rows)
    return arr.view("V16").reshape(rows)


def _batch(keys: np.ndarray, with_meta: bool) -> RecordBatch:
    rows = len(keys)
    rng = np.random.default_rng(11)
    cols = {"k": keys, "v": rng.random(rows).astype(np.float32),
            "vec": rng.random((rows, 3))}
    if not with_meta:
        return RecordBatch(cols)
    return RecordBatch(cols,
                       timestamps=rng.integers(0, 10 ** 6, rows),
                       key_ids=rng.integers(0, 99, rows).astype(np.int32))


def _masked_split(batch: RecordBatch, targets: int, max_parallelism: int):
    """The split as it was: one boolean mask per target over every column."""
    kg = keygroups.assign_to_key_group(
        keygroups.hash_keys(batch.column("k")), max_parallelism)
    target = (np.asarray(kg, np.int64) * targets) // max_parallelism
    parts = {}
    for t in range(targets):
        sel = target == t
        if sel.any():
            parts[t] = {
                "columns": {n: np.asarray(c)[sel]
                            for n, c in batch.columns.items()},
                "timestamps": (None if batch.timestamps is None
                               else np.asarray(batch.timestamps)[sel]),
                "key_ids": (None if batch.key_ids is None
                            else np.asarray(batch.key_ids)[sel]),
                "key_groups": kg[sel]}
    return parts


def _dispatch(batch: RecordBatch, targets: int, max_parallelism: int,
              computes: int = None):
    channels = [LocalChannel(8) for _ in range(targets)]
    d = OutputDispatcher("hash", channels, max_parallelism=max_parallelism,
                         key_column="k")
    d.emit(batch)
    if computes is not None:
        assert d.key_groups_computed == computes
    got = {}
    for t, ch in enumerate(channels):
        assert len(ch) <= 1          # one part a target, none when empty
        part = ch.poll()
        if part is not None:
            got[t] = part
    return got


def _same(a, b):
    if a is None or b is None:
        assert a is None and b is None
        return
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape
    assert a.tobytes() == b.tobytes() if a.dtype != object \
        else list(a) == list(b)


def _assert_parts(got, want, max_parallelism: int):
    assert sorted(got) == sorted(want)
    for t, part in got.items():
        ref = want[t]
        assert list(part.columns) == list(ref["columns"])
        for name, col in ref["columns"].items():
            _same(part.column(name), col)
        _same(part.timestamps, ref["timestamps"])
        _same(part.key_ids, ref["key_ids"])
        assert part.key_spec == ("k", max_parallelism)
        _same(part.key_groups, ref["key_groups"])
        _same(part.key_groups, keygroups.assign_to_key_group(
            keygroups.hash_keys(part.column("k")), max_parallelism))


@pytest.mark.parametrize("with_meta", [True, False],
                         ids=["ids+timestamps", "bare"])
@pytest.mark.parametrize("kind", ["int64", "int32", "string", "composite"])
@pytest.mark.parametrize("max_parallelism", [128, 4096])
@pytest.mark.parametrize("targets", [1, 2, 3, 4, 7])
def test_split_matches_the_masked_split(targets, max_parallelism, kind,
                                        with_meta):
    batch = _batch(_keys(kind), with_meta)
    got = _dispatch(batch, targets, max_parallelism)
    _assert_parts(got, _masked_split(batch, targets, max_parallelism),
                  max_parallelism)
    assert sum(len(p) for p in got.values()) == ROWS


@pytest.mark.parametrize("targets", [1, 2, 3, 4, 7])
def test_empty_batch_puts_nothing(targets):
    batch = _batch(_keys("int64", rows=0), True)
    assert _dispatch(batch, targets, 128) == {}


@pytest.mark.parametrize("max_parallelism", [128, 4096])
@pytest.mark.parametrize("targets", [2, 3, 4, 7])
def test_all_rows_to_one_target(targets, max_parallelism):
    batch = _batch(np.full(ROWS, 424242, np.int64), True)
    got = _dispatch(batch, targets, max_parallelism)
    assert len(got) == 1             # the empty parts are not put
    _assert_parts(got, _masked_split(batch, targets, max_parallelism),
                  max_parallelism)


@pytest.mark.parametrize("max_parallelism,parallelism", [
    (128, 1), (128, 2), (4096, 7), (32768, 32768), (1 << 20, 4096)],
    ids=["one", "two", "seven", "flinks-bound", "beyond-32-bits"])
def test_rows_by_target_is_the_references_operator_index(max_parallelism,
                                                         parallelism):
    kg = np.random.default_rng(3).integers(
        0, max_parallelism, ROWS).astype(np.int32)
    order, bounds = keygroups.rows_by_target(kg, max_parallelism, parallelism)
    target = np.asarray([keygroups.compute_operator_index_for_key_group(
        max_parallelism, parallelism, int(g)) for g in kg])
    _same(order, np.argsort(target, kind="stable"))
    assert bounds == [0, *np.cumsum(
        np.bincount(target, minlength=parallelism)).tolist()]


def test_parts_are_put_in_target_order_as_they_are_cut():
    """A consumer must not wait for the parts of the others: with the
    second channel full, the first target's part is already there."""
    import threading
    ch0, ch1 = LocalChannel(1), LocalChannel(1)
    ch1.put(RecordBatch({"k": np.zeros(1, np.int64)}))      # no credit
    d = OutputDispatcher("hash", [ch0, ch1], key_column="k")
    t = threading.Thread(target=d.emit,
                         args=(_batch(_keys("int64"), False),), daemon=True)
    t.start()
    part = ch0.poll(timeout_s=5.0)
    assert part is not None and t.is_alive()    # blocked on ch1 only
    ch1.poll()
    t.join(5.0)
    assert not t.is_alive() and len(ch1) == 1


def test_batch_with_unnamed_key_groups_routes_by_them():
    """Key groups handed in as an array (no key named) stay the routing
    truth, whatever the edge's key column hashes to."""
    keys = _keys("int64", 64)
    kg = np.arange(64, dtype=np.int32) % 128
    got = _dispatch(RecordBatch({"k": keys}, key_groups=kg), 4, 128)
    for t, part in got.items():
        assert part.key_spec is None
        assert ((np.asarray(part.key_groups, np.int64) * 4) // 128 == t).all()


def test_batch_keyed_on_another_column_is_routed_by_the_edges_key():
    """The edge's key decides: key groups of another key_by (another
    column, or another max_parallelism) are not this exchange's."""
    a, b = _keys("int64", seed=1), _keys("int64", seed=2)
    batch = RecordBatch({"k": a, "other": b}).keyed_by("other", 128)
    assert batch.key_groups is not None          # derived, for "other"
    for t, part in _dispatch(batch, 2, 128).items():
        want = keygroups.route_raw_keys(part.column("k"), 2, 128)
        assert (want == t).all()
        assert part.key_spec == ("k", 128)
    wide = RecordBatch({"k": a}).keyed_by("k", 4096)
    for t, part in _dispatch(wide, 2, 128).items():
        assert part.key_spec == ("k", 128)
        assert (keygroups.route_raw_keys(part.column("k"), 2, 128) == t).all()


# ---------------------------------------------------------------------------
# at most once, and only for a reader
# ---------------------------------------------------------------------------

def _counted(op: KeyByOperator):
    return {"carried": op.key_groups_carried, "unread": op.key_groups_unread}


def test_key_by_derives_nothing_until_somebody_reads():
    batch = _batch(_keys("int64"), True)
    op = KeyByOperator("k", 128)
    (keyed,) = op.process_batch(batch)
    assert _counted(op) == {"carried": 0, "unread": ROWS}
    assert keyed.key_spec == ("k", 128) and not keyed.key_groups_derived
    kg = keyed.key_groups
    assert keyed.key_groups_derived
    _same(kg, keygroups.assign_to_key_group(
        keygroups.hash_keys(batch.column("k")), 128))
    assert keyed.key_groups is kg            # kept, not derived again


@pytest.mark.parametrize("targets,computes", [(1, 0), (2, ROWS), (7, ROWS)])
def test_key_by_finds_the_exchanges_key_groups_carried(targets, computes):
    """A hash edge with several targets reads the key groups and its parts
    carry them; one with a single target reads nothing and derives none."""
    batch = _batch(_keys("string"), False)
    parts = _dispatch(batch, targets, 128, computes=computes)
    op = KeyByOperator("k", 128)
    for part in parts.values():
        (out,) = op.process_batch(part)
        assert out is part and part.key_groups_derived == (targets > 1)
    assert _counted(op) == (
        {"carried": ROWS, "unread": 0} if targets > 1
        else {"carried": 0, "unread": ROWS})


def test_exchange_does_not_derive_what_the_batch_carries():
    batch = _batch(_keys("int64"), False).keyed_by("k", 128)
    assert batch.key_groups is not None
    _dispatch(batch, 2, 128, computes=0)


@pytest.mark.parametrize("column,max_parallelism",
                         [("other", 128), ("k", 4096)])
def test_key_by_on_another_key_replaces(column, max_parallelism):
    keys = _keys("int64")
    first = RecordBatch({"k": keys, "other": keys[::-1].copy()}) \
        .keyed_by("k", 128)
    assert first.key_groups is not None
    op = KeyByOperator(column, max_parallelism)
    (out,) = op.process_batch(first)
    assert _counted(op) == {"carried": 0, "unread": ROWS}
    assert out.key_spec == (column, max_parallelism)
    assert not out.key_groups_derived
    _same(out.key_groups, keygroups.assign_to_key_group(
        keygroups.hash_keys(out.column(column)), max_parallelism))
    _same(first.key_groups, keygroups.assign_to_key_group(
        keygroups.hash_keys(keys), 128))


def test_key_by_of_a_missing_column_fails_at_once():
    with pytest.raises(KeyError):
        KeyByOperator("nope").process_batch(_batch(_keys("int64"), False))


_HANDERS = {
    "select": lambda b: b.select(np.arange(len(b)) % 3 == 0),
    "take": lambda b: b.take(np.arange(len(b))[::-2]),
    "concat": lambda b: RecordBatch.concat([b.take(np.arange(10)),
                                            b.take(np.arange(10, len(b)))]),
    "with_columns": lambda b: b.with_columns(
        dict(b.columns, v=np.asarray(b.column("v")) * 2)),
    "with_timestamps": lambda b: b.with_timestamps(
        np.arange(len(b), dtype=np.int64)),
    "with_keys": lambda b: b.with_keys(np.zeros(len(b), np.int32)),
    "pickle": lambda b: pickle.loads(pickle.dumps(b)),
    "codec": lambda b: codec.decode_batch(codec.encode_batch(b)),
}


@pytest.mark.parametrize("derived", [False, True], ids=["unread", "derived"])
@pytest.mark.parametrize("how", sorted(_HANDERS))
def test_handing_on_neither_forces_nor_loses(how, derived):
    keyed = _batch(_keys("int64"), True).keyed_by("k", 128)
    if derived:
        assert keyed.key_groups is not None
    out = _HANDERS[how](keyed)
    assert keyed.key_groups_derived == derived       # nothing forced
    assert out.key_spec == ("k", 128)
    # the codec ships a named key's name, never its key groups
    assert out.key_groups_derived == (derived and how != "codec")
    _same(out.key_groups, keygroups.assign_to_key_group(
        keygroups.hash_keys(out.column("k")), 128))


def test_concat_of_read_and_unread_parts_derives_only_the_unread():
    keyed = _batch(_keys("int64"), False).keyed_by("k", 128)
    head, tail = keyed.take(np.arange(100)), keyed.take(np.arange(100, ROWS))
    assert head.key_groups is not None
    out = RecordBatch.concat([head, tail])
    assert out.key_groups_derived and tail.key_groups_derived
    _same(out.key_groups, keygroups.assign_to_key_group(
        keygroups.hash_keys(out.column("k")), 128))


def test_concat_of_keyed_and_unkeyed_is_refused():
    b = _batch(_keys("int64"), False)
    with pytest.raises(ValueError, match="key"):
        RecordBatch.concat([b, b.keyed_by("k", 128)])


def test_replacing_the_key_column_keeps_the_key_groups_of_the_old_key():
    """As before this mechanism: key groups are those of the key the
    records were keyed by, whatever a later map writes over the column."""
    keyed = _batch(_keys("int64"), False).keyed_by("k", 128)
    (out,) = MapOperator(lambda c: dict(c, k=np.asarray(c["k"]) + 1)) \
        .process_batch(keyed)
    assert out.key_spec is None
    _same(out.key_groups, keygroups.assign_to_key_group(
        keygroups.hash_keys(keyed.column("k")), 128))


def test_pickle_reads_the_slots_form_of_older_snapshots():
    kg = np.arange(4, dtype=np.int32)
    old_state = (None, {"columns": {"k": np.arange(4)}, "timestamps": None,
                        "key_ids": None, "key_groups": kg, "_size": 4})
    b = RecordBatch.__new__(RecordBatch)
    b.__setstate__(old_state)
    assert len(b) == 4 and b.key_spec is None
    _same(b.key_groups, kg)


def test_with_keys_handed_key_groups_replace_the_batchs_own():
    keyed = _batch(_keys("int64"), False).keyed_by("k", 128)
    kg = np.zeros(ROWS, np.int32)
    out = keyed.with_keys(np.arange(ROWS, dtype=np.int32), kg)
    assert out.key_spec is None and out.key_groups is kg


def test_consumer_behind_the_wire_derives_the_same_key_groups():
    """At most once holds inside one process: a part's key groups stay
    behind, the key's name crosses, and a reader on the other side (a later
    hash edge, a user function) derives the values the producer routed by."""
    parts = _dispatch(_batch(_keys("composite"), True), 3, 4096,
                      computes=ROWS)
    op = KeyByOperator("k", 4096)
    for t, part in parts.items():
        wire = codec.encode_batch(part)
        assert len(wire) < len(codec.encode_batch(
            RecordBatch(part.columns, part.timestamps, part.key_ids,
                        part.key_groups)))       # no 4 bytes a record
        got = codec.decode_batch(wire)
        (out,) = op.process_batch(got)
        assert out is got and not out.key_groups_derived
        _same(out.key_groups, part.key_groups)
        assert (keygroups.rows_by_target(out.key_groups, 4096, 3)[1]
                == [0] * (t + 1) + [len(part)] * (3 - t))
    assert _counted(op) == {"carried": 0, "unread": ROWS}


def test_codec_ships_unnamed_key_groups_as_before():
    kg = (np.arange(ROWS) % 16).astype(np.int32)
    b = RecordBatch({"k": _keys("int64")}, key_groups=kg)
    out = codec.decode_batch(codec.encode_batch(b))
    assert out.key_spec is None
    _same(out.key_groups, kg)
    bare = RecordBatch({"k": _keys("int64")})
    # an unkeyed batch is byte for byte what it was
    assert codec.encode_batch(bare)[4] == 0
    assert codec.decode_batch(codec.encode_batch(bare)).key_groups is None


@pytest.mark.parametrize("derived", [False, True], ids=["unread", "derived"])
@pytest.mark.parametrize("new_parallelism", [1, 2, 3, 4])
def test_channel_state_of_keyed_parts_rescales_like_the_live_edge(
        new_parallelism, derived):
    """Unaligned checkpoints pickle queued batches; a restore at another
    parallelism routes each by its key as the live dispatcher would."""
    from flink_tpu.state.redistribute import _route_batch
    batch = _batch(_keys("int64"), True)
    queued = batch.keyed_by("k", 128)
    if derived:
        assert queued.key_groups is not None
    queued = pickle.loads(pickle.dumps(queued))
    info = {"partitioning": "hash", "key_column": "k",
            "max_parallelism": 128}
    got = dict(_route_batch(queued, info, new_parallelism))
    _assert_parts(got, _masked_split(batch, new_parallelism, 128), 128)


# -- through the runtimes ----------------------------------------------------

class _ReadKeyGroups(KeyedProcessFunction):
    """A user function that reads what ``key_by`` promises it."""

    def process_batch(self, ctx, batch):
        return RecordBatch({"k": batch.column("k"),
                            "kg": np.asarray(batch.key_groups)})


def _rows(n=600):
    return [{"k": i % 37, "other": i % 11, "v": 1.0, "ts": i}
            for i in range(n)]


def _env(parallelism):
    from flink_tpu.datastream.api import StreamExecutionEnvironment
    env = StreamExecutionEnvironment.get_execution_environment()
    env.set_parallelism(parallelism)
    return env


def _window_sum(stream, key):
    import jax.numpy as jnp
    from flink_tpu.core.functions import SumAggregator
    from flink_tpu.windowing.assigners import TumblingEventTimeWindows
    return (stream.key_by(key).window(TumblingEventTimeWindows.of(1000))
            .aggregate(SumAggregator(jnp.float32), value_column="v"))


def _source(env, rows):
    return (env.from_collection(rows, timestamp_column="ts")
            .assign_timestamps_and_watermarks(0, timestamp_column="ts"))


def _tasks(env):
    from flink_tpu.cluster.task import SourceSubtask
    tasks = env.last_cluster.tasks()
    return ([t for t in tasks if isinstance(t, SourceSubtask)],
            [t for t in tasks if not isinstance(t, SourceSubtask)])


def test_cluster_computes_key_groups_once_a_record_on_the_sources():
    env = _env(2)
    sink = _window_sum(_source(env, _rows()), "k").collect()
    env.execute_cluster("keyed-exchange")
    assert sorted((r["k"], r["result"]) for r in sink.rows()) == sorted(
        (k, float(sum(1 for r in _rows() if r["k"] == k))) for k in range(37))
    sources, windows = _tasks(env)
    assert len(sources) == 2 and len(windows) == 2
    out = sum(t.records_out for t in sources)
    assert out == 600
    assert sum(t.key_group_records["computed"] for t in sources) == out
    for t in windows:
        assert t.key_group_records == {
            "computed": 0, "carried": t.records_in, "unread": 0}
    assert sum(t.records_in for t in windows) == out
    status = env.last_cluster.job_status()
    assert sum(s["key_group_records"]["computed"]
               for v in status["vertices"] for s in v["subtasks"]) == out


def test_one_target_edge_whose_consumer_never_reads_computes_nothing():
    env = _env(1)
    sink = _window_sum(_source(env, _rows()), "k").collect()
    env.execute_cluster("one-target")
    assert len(sink.rows()) == 37
    sources, windows = _tasks(env)
    assert [t.key_group_records["computed"] for t in sources + windows] \
        == [0, 0]
    assert windows[0].key_group_records["unread"] == 600


def test_second_key_by_on_another_column_routes_and_rekeys_by_it():
    """Every record of one key reaches one subtask, whichever key_by came
    before: the sums per second key are whole."""
    env = _env(2)
    first = _source(env, _rows()).key_by("k") \
        .map(lambda c: dict(c, v=np.asarray(c["v"]) * 2))
    sink = _window_sum(first, "other").collect()
    env.execute_cluster("rekey")
    assert sorted((r["other"], r["result"]) for r in sink.rows()) == sorted(
        (o, 2.0 * sum(1 for r in _rows() if r["other"] == o))
        for o in range(11))
    sources, tasks = _tasks(env)
    # each edge derives its own key's groups once a record: 600 on the
    # sources (for "k"), 600 on the map's tasks (for "other")
    assert sum(t.key_group_records["computed"] for t in sources) == 600
    assert sum(t.key_group_records["computed"] for t in tasks) == 600


@pytest.mark.parametrize("cluster", [False, True], ids=["local", "cluster"])
def test_user_operator_reads_todays_key_groups(cluster):
    env = _env(2 if cluster else 1)
    stream = _source(env, _rows()).key_by("k").process(_ReadKeyGroups())
    if cluster:
        sink = stream.collect()
        env.execute_cluster("reader")
        rows = sink.rows()
    else:
        rows = stream.execute_and_collect()
    assert len(rows) == 600
    k = np.asarray([r["k"] for r in rows])
    want = keygroups.assign_to_key_group(keygroups.hash_keys(k),
                                         env.max_parallelism)
    assert [r["kg"] for r in rows] == want.tolist()
