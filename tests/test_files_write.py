"""A write's files side by side on the shared pool, and the stats of an
arrow-backed column from its arrow array.

`KeyValueFileWriterFactory.write` (core/datafile.py) cuts its batch into
files of target size; where the cut gives several and the caller is no thread
of the shared pool, each file is a task on it (`parallel/pipeline.py:
bounded_map`). The tests hold that the pool and the loop in turn give the
same list and the same files, that a pool task writes in turn, that a failed
file raises once nothing is being written any more, and that the mechanism's
span and counters say what happened. `collect_stats` (format/__init__.py)
takes the minimum and maximum of an arrow-backed STRING or BINARY column in
arrow's kernel: the same FieldStats as the object-array path, case by case,
and no Python string a row.
"""

import threading
from dataclasses import replace

import numpy as np
import pyarrow as pa
import pytest

import paimon_tpu as pt
from paimon_tpu.core.datafile import KeyValueFileReaderFactory, KeyValueFileWriterFactory
from paimon_tpu.core.kv import KVBatch
from paimon_tpu.data.batch import Column, ColumnBatch
from paimon_tpu.format import collect_stats
from paimon_tpu.fs import LocalFileIO
from paimon_tpu.metrics import registry, span
from paimon_tpu.parallel.pipeline import bounded_map
from paimon_tpu.types import RowKind
from paimon_tpu.utils import SHARED_POOL_THREAD_PREFIX, shared_executor

from test_tracing import _inside, _named, traced  # noqa: E402

SCHEMA = pt.RowType.of(("id", pt.BIGINT(False)), ("v", pt.DOUBLE()), ("s", pt.STRING()), ("b", pt.BYTES()))
ROWS, ROWS_A_FILE = 6_000, 1_100  # six files: five of 1,100 rows and one of 500
ROW_BYTES = 8 + 8 + 16 + 16  # what _estimate_row_bytes gives SCHEMA


class RecordingFactory(KeyValueFileWriterFactory):
    """The factory under test, noting which thread wrote each file and how
    many files are being written at the moment."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.threads: list[str] = []
        self.active = 0
        self._lock = threading.Lock()

    def _write_one(self, *args, **kwargs):
        with self._lock:
            self.threads.append(threading.current_thread().name)
            self.active += 1
        try:
            return super()._write_one(*args, **kwargs)
        finally:
            with self._lock:
                self.active -= 1


def _factory(directory, file_format="parquet", keyed=True, parallelism=None, file_io=None, rows_a_file=ROWS_A_FILE):
    return RecordingFactory(
        file_io or LocalFileIO(), str(directory), SCHEMA, ["id"] if keyed else [], 0, file_format=file_format,
        target_file_size=ROW_BYTES * rows_a_file, keyed=keyed, parallelism=parallelism)


def _batch(rows=ROWS, sorted_input=True):
    """STRING and BYTES arrow-backed with nulls, as a decode or a gather
    leaves them; in key order, or in an event order that is not."""
    rng = np.random.default_rng(11)
    ids = np.arange(rows, dtype=np.int64) * 3
    if not sorted_input:
        ids = rng.permutation(ids)
    strings = [None if i % 7 == 0 else f"s{int(i) % 389:04d}-é" for i in ids]
    s = pa.array(strings, type=pa.string())
    b = pa.array([None if x is None else x.encode() for x in strings], type=pa.binary())
    data = ColumnBatch(SCHEMA, {
        "id": Column(ids),
        "v": Column(rng.random(rows)),
        "s": Column(validity=np.asarray(s.is_valid()), arrow=s),
        "b": Column(validity=np.asarray(b.is_valid()), arrow=b),
    })
    kinds = np.where(ids % 5 == 0, int(RowKind.DELETE), int(RowKind.INSERT)).astype(np.uint8)
    return KVBatch.from_rows(data, 100, kinds)


def _rows(kv):
    return kv.data.to_pylist(), kv.seq.tolist(), kv.kind.tolist()


def _counters():
    g = registry.group("datafile")
    return g.counter("files_written").count, g.counter("files_written_on_pool").count


@pytest.mark.parametrize("sorted_input", [True, False], ids=["key-order", "event-order"])
@pytest.mark.parametrize("keyed", [True, False], ids=["keyed", "append-only"])
@pytest.mark.parametrize("file_format", ["orc", "parquet"])
def test_the_pool_and_the_loop_in_turn_write_the_same_files(tmp_path, file_format, keyed, sorted_input):
    kv = _batch(sorted_input=sorted_input)
    written = {}
    for name, parallelism in (("pool", None), ("in-turn", 1)):
        factory = _factory(tmp_path / name, file_format, keyed, parallelism)
        before = _counters()
        metas = factory.write(kv, level=3, file_source="compact", sorted_input=sorted_input)
        files, on_pool = (after - b for after, b in zip(_counters(), before))
        assert len(metas) == 6 and files == 6
        me = threading.current_thread().name
        if parallelism is None:
            assert on_pool == 6 and all(t.startswith(SHARED_POOL_THREAD_PREFIX) for t in factory.threads)
        else:
            assert on_pool == 0 and factory.threads == [me] * 6
        reader = KeyValueFileReaderFactory(LocalFileIO(), str(tmp_path / name), SCHEMA, {0: SCHEMA}, file_format, keyed)
        written[name] = (metas, [_rows(reader.read(m)) for m in metas])
    (pool, pool_rows), (turn, turn_rows) = written["pool"], written["in-turn"]
    # every field but the uuid name and the clock: order, row_count, min_key/max_key, key_stats/value_stats,
    # the sequence bounds, file_size, level, delete_row_count
    assert [replace(m, file_name="", creation_time_millis=0) for m in pool] == \
        [replace(m, file_name="", creation_time_millis=0) for m in turn]
    assert len({m.file_name for m in pool}) == 6
    assert [m.row_count for m in pool] == [1_100] * 5 + [500]
    if keyed and sorted_input:
        assert all(a.max_key < b.min_key for a, b in zip(pool, pool[1:]))
    assert pool_rows == turn_rows
    # the files hold the batch, cut by position: event order is kept inside and across files
    data, seq, kind = _rows(kv)
    if not keyed:  # an append-only file keeps no system columns
        seq, kind = [0] * ROWS, [0] * ROWS
    assert [r for rows, _, _ in pool_rows for r in rows] == data
    assert [x for _, s, _ in pool_rows for x in s] == seq and [x for _, _, k in pool_rows for x in k] == kind


def test_a_write_inside_a_pool_task_writes_in_turn_and_the_pool_is_not_exhausted(tmp_path):
    """As many writing tasks as twice the pool's width, every one on a pool
    thread: were their files tasks of the same pool, the first sixteen would
    wait for files queued behind the other sixteen for ever."""
    kv = _batch(rows=50)
    width = shared_executor()._max_workers
    factories = [_factory(tmp_path / str(i), rows_a_file=10) for i in range(2 * width)]
    before = _counters()
    metas = bounded_map(lambda f: f.write(kv, level=0), factories)
    files, on_pool = (after - b for after, b in zip(_counters(), before))
    assert [len(m) for m in metas] == [5] * len(factories)
    assert files == 5 * len(factories) and on_pool == 0
    for f in factories:  # each write's five files on the one pool thread that ran the write
        assert len(set(f.threads)) == 1 and f.threads[0].startswith(SHARED_POOL_THREAD_PREFIX)


def test_one_file_is_written_on_the_callers_thread(tmp_path):
    factory = _factory(tmp_path, rows_a_file=ROWS)
    before = _counters()
    assert len(factory.write(_batch(), level=0)) == 1
    assert factory.threads == [threading.current_thread().name]
    assert tuple(a - b for a, b in zip(_counters(), before)) == (1, 0)


class ThirdWriteFails(LocalFileIO):
    def __init__(self):
        self.calls = 0
        self._lock = threading.Lock()

    def write_bytes(self, path, data, overwrite=False):
        with self._lock:
            self.calls += 1
            nth = self.calls
        if nth == 3:
            raise OSError("the third file does not land")
        super().write_bytes(path, data, overwrite)


@pytest.mark.parametrize("parallelism", [None, 1], ids=["pool", "in-turn"])
def test_a_failed_file_raises_from_write_once_no_file_is_being_written(tmp_path, parallelism):
    io = ThirdWriteFails()
    factory = _factory(tmp_path, parallelism=parallelism, file_io=io)
    before = _counters()
    with pytest.raises(OSError, match="third file"):
        factory.write(_batch(), level=0)
    # at rest: every file.write that began has ended, none begins later, and the pool serves the next caller
    assert factory.active == 0
    calls, started = io.calls, len(factory.threads)
    assert bounded_map(lambda x: x + 1, list(range(64))) == list(range(1, 65))
    assert (io.calls, len(factory.threads), factory.active) == (calls, started, 0)
    assert _counters() == before  # a write that raised counts no file
    assert len(list(tmp_path.iterdir())) == calls - 1  # orphans, as from the loop in turn
    if parallelism == 1:
        assert calls == 3  # the loop stops at the failure


@pytest.fixture(scope="module")
def traced_writes(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("files-write")
    kv = _batch()
    with traced(tmp / "trace") as events:
        with span("compact", new_op=True) as caller:
            _factory(tmp / "pool", "orc").write(kv, level=5, file_source="compact")
        _factory(tmp / "in-turn", parallelism=1).write(kv, level=0)
        _factory(tmp / "one", rows_a_file=ROWS).write(kv, level=0)
    return {"events": events, "op": caller.op}


def test_files_write_spans_the_fan_out_and_the_wait_on_the_callers_thread(traced_writes):
    events = traced_writes["events"]
    fans = sorted(_named(events, "files.write"), key=lambda e: e[1])
    assert len(fans) == 2  # the write of one file opens none
    pool, turn = fans
    assert (pool[4]["files"], pool[4]["rows"], pool[4]["on_pool"]) == (6, ROWS, 1)
    assert (turn[4]["files"], turn[4]["rows"], turn[4]["on_pool"]) == (6, ROWS, 0)
    assert pool[4]["op"] == traced_writes["op"] and pool[4]["parent"] == "compact"
    assert pool[3] == turn[3] == _named(events, "compact")[0][3]  # the caller's line
    files = sorted(_named(events, "file.write"), key=lambda e: e[1])
    assert len(files) == 13
    on_pool = [f for f in files if pool[1] <= f[1] and f[2] <= pool[2]]
    assert len(on_pool) == 6 and all(f[3] != pool[3] for f in on_pool)  # in time inside it, on other lines
    for f in on_pool:  # a file written over there names the operation and the span that asked
        assert f[4]["op"] == traced_writes["op"] and f[4]["parent"] == "files.write"
        assert (f[4]["level"], f[4]["format"]) == (5, "orc") and f[4]["bytes"] > 0
    assert sorted(f[4]["rows"] for f in on_pool) == [500] + [1_100] * 5
    in_turn = [f for f in files if _inside(f, turn)]
    assert len(in_turn) == 6 and all(f[3] == turn[3] and f[4]["parent"] == "files.write" for f in in_turn)
    (single,) = [f for f in files if f not in on_pool and f not in in_turn]
    assert single[3] == pool[3] and single[4]["rows"] == ROWS and "parent" not in single[4]


# ---- collect_stats over arrow-backed columns ------------------------------------------------------------

LONG = "k" * 15  # with one more character: longer than the truncation length of 16
CASES = {
    # values, and which of them a validity kept beside the array hides (None: the array's own nulls)
    "nulls-in-the-arrow-array": ([None, "pear", "apple", None, "quince", "fig"], None),
    "validity-beside-the-array": (["aaa", "pear", "apple", "zzz", "quince", "fig"], [0, 3]),
    "validity-beside-and-nulls-within": (["aaa", None, "apple", "zzz", "quince", None], [0, 3]),
    "all-null": ([None, None, None], None),
    "empty-strings": (["", "b", "", "a"], None),
    "one-value": (["only"], None),
    # U+FFEE sorts below U+1F600 by code point and by UTF-8 byte, above it by UTF-16 unit
    "non-ascii-and-astral": (["z", "￮", "é", "\U0001f600", "~", "\U00010000"], None),
    "longer-than-the-truncation": ([LONG + "a-tail", LONG + "z-tail", LONG + "m"], None),
    "truncated-maximum-carries": ([LONG[:-1] + "\U0010ffff\U0010ffff-tail", "a"], None),
}


def _both_ways(values, hidden, binary, offset=0):
    """The same column arrow-backed and as an object array, under one validity."""
    if binary:
        values = [None if v is None else v.encode() for v in values]
    validity = np.array([v is not None for v in values])
    if hidden is not None:
        validity[hidden] = False
    dtype = pt.BYTES() if binary else pt.STRING()
    schema = pt.RowType.of(("c", dtype))
    arr = pa.array(values, type=pa.binary() if binary else pa.string())
    objects = np.empty(len(values), dtype=object)
    objects[:] = values
    if offset:
        arr, objects, validity = arr.slice(offset), objects[offset:], validity[offset:]
    arrow = ColumnBatch(schema, {"c": Column(validity=validity.copy(), arrow=arr)})
    plain = ColumnBatch(schema, {"c": Column(objects, validity.copy())})
    return arrow, plain


@pytest.mark.parametrize("binary", [False, True], ids=["STRING", "BINARY"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_stats_of_an_arrow_backed_column_equal_the_object_paths(case, binary):
    values, hidden = CASES[case]
    arrow, plain = _both_ways(values, hidden, binary)
    got, want = collect_stats(arrow), collect_stats(plain)
    assert got == want
    assert type(got["c"].min) is type(want["c"].min)
    assert arrow.column("c")._values is None  # no Python object a row
    if case == "all-null":
        assert got["c"] == type(got["c"])(None, None, 3, 3)
    if case == "longer-than-the-truncation":  # the bumped maximum stays an upper bound
        top = LONG + "{"
        assert got["c"].max == (top.encode() if binary else top) and len(got["c"].min) == 16


@pytest.mark.parametrize("binary", [False, True], ids=["STRING", "BINARY"])
def test_stats_of_a_sliced_arrow_array_are_the_slices(binary):
    values = ["0-before-the-slice", "~~-before-the-slice", "pear", None, "apple", "quince"]
    arrow, plain = _both_ways(values, None, binary, offset=2)
    assert arrow.column("c").arrow.offset == 2
    got = collect_stats(arrow)
    assert got == collect_stats(plain) and got["c"].null_count == 1 and got["c"].row_count == 4
    assert got["c"].min == (b"apple" if binary else "apple") and got["c"].max == (b"quince" if binary else "quince")
    assert arrow.column("c")._values is None


def test_a_written_files_stats_never_materialize_its_string_columns(tmp_path):
    kv = _batch()
    metas = _factory(tmp_path, "orc").write(kv, level=0)
    assert kv.data.column("s")._values is None and kv.data.column("b")._values is None
    plain = ColumnBatch(SCHEMA, {n: Column(kv.data.column(n).values, kv.data.column(n).validity) for n in SCHEMA.field_names})
    whole = collect_stats(plain)
    assert min(m.value_stats["s"].min for m in metas) == whole["s"].min
    assert max(m.value_stats["b"].max for m in metas) == whole["b"].max
    assert sum(m.value_stats["s"].null_count for m in metas) == whole["s"].null_count
