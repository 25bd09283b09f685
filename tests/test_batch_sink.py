"""BatchSink: the result of a read of several splits, built once.

The sink must give exactly concat_batches of the batches appended: values,
validity, dtype and backing of every column, whether a batch was written into
its rows beforehand (reserve) or copied in (append). TableRead.read_all builds
its result through it; a split whose continuation runs on the reading thread
in split order (scan.prefetch-splits=0 here, merge.engine=mesh in
test_mesh_exec.py) writes its winners in place, and read{rows_placed} /
read{rows_joined} say how many cells went which way.
"""

import numpy as np
import pyarrow as pa
import pytest

import paimon_tpu as pt
import paimon_tpu.data.batch as batch_module
from paimon_tpu.catalog import FileSystemCatalog
from paimon_tpu.data.batch import BatchSink, Column, ColumnBatch, concat_batches
from paimon_tpu.data.predicate import PredicateBuilder
from paimon_tpu.metrics import registry
from test_take_from_parts import assert_same_column

SCHEMA = pt.RowType.of(("id", pt.BIGINT(False)), ("c", pt.BIGINT()), ("d", pt.DOUBLE()), ("s", pt.STRING()))
LENGTHS = (5, 0, 7, 3)  # the second batch is empty
_POOL = np.array(["a", "b", "c"], dtype=object)


def assert_same_batch(got: ColumnBatch, want: ColumnBatch):
    assert got.schema.field_names == want.schema.field_names and got.num_rows == want.num_rows
    for name in want.schema.field_names:
        assert_same_column(got.column(name), want.column(name))


def _batch(rng, n, part, c=None, s=None):
    """One batch of SCHEMA; `c` and `s` make those columns (default: int64 values, arrow strings)."""
    cols = {
        "id": Column(rng.integers(0, 1 << 40, n)),
        "c": c(rng, n, part) if c else Column(rng.integers(-9, 9, n)),
        "d": Column(rng.normal(size=n)),
        "s": s(rng, n, part) if s else Column(arrow=pa.array([f"s{rng.integers(9)}" for _ in range(n)], type=pa.string())),
    }
    return ColumnBatch(SCHEMA, cols)


SINK_CASES = {
    "fixed-width-and-arrow": {},
    "validity-in-the-third-batch-only": {"c": lambda rng, n, part: Column(rng.integers(0, 9, n), rng.random(n) < 0.5 if part == 2 else None)},
    "validity-in-the-first-batch-only": {"c": lambda rng, n, part: Column(rng.integers(0, 9, n), rng.random(n) < 0.5 if part == 0 else None)},
    "code-backed-strings": {"s": lambda rng, n, part: Column.from_codes(_POOL, rng.integers(0, 3, n).astype(np.uint32))},
    "object-valued-strings": {"s": lambda rng, n, part: Column(np.array([f"o{i}" for i in range(n)], dtype=object))},
    "a-code-backed-batch-of-a-fixed-width-column": {
        "c": lambda rng, n, part: Column.from_codes(np.arange(4), rng.integers(0, 4, n).astype(np.uint32)) if part == 2
        else Column(rng.integers(0, 9, n), rng.random(n) < 0.5)},
    "a-batch-of-another-dtype": {"c": lambda rng, n, part: Column(rng.integers(0, 9, n).astype(np.int32 if part == 2 else np.int64))},
    "another-dtype-first": {"c": lambda rng, n, part: Column(rng.integers(0, 9, n).astype(np.int32 if part == 0 else np.int64))},
}
# the fixed-width columns that keep their one array to the end: the others fall back to their parts
ARRAYS = {case: 3 for case in SINK_CASES} | {
    "a-code-backed-batch-of-a-fixed-width-column": 2, "a-batch-of-another-dtype": 2, "another-dtype-first": 2}


@pytest.mark.parametrize("in_place", [False, True], ids=["appended", "reserved"])
@pytest.mark.parametrize("case", SINK_CASES)
def test_the_sink_gives_the_concatenation_of_its_batches(case, in_place):
    def fresh():
        rng = np.random.default_rng(11)
        return [_batch(rng, n, part, **SINK_CASES[case]) for part, n in enumerate(LENGTHS)]

    want = concat_batches(fresh())
    sink = BatchSink(SCHEMA, sum(LENGTHS) + 4)  # the plan's bound: room the result does not use
    for b in fresh():
        if in_place:  # a producer that writes its numpy-valued columns where the sink says
            dest = sink.reserve(b.num_rows)
            cols = dict(b.columns)
            for name, out in dest.items():
                if cols[name]._values is not None and cols[name]._values.dtype == out.dtype:
                    out[:] = cols[name]._values
                    cols[name] = Column(out, cols[name].validity)
            b = ColumnBatch(SCHEMA, cols)
        sink.append(b)
    # a column that fell back to its parts was not written into the result after all
    placed = ARRAYS[case] * sum(LENGTHS) if in_place else 0
    got = sink.result()
    assert_same_batch(got, want)
    assert sink.rows == sum(LENGTHS)
    assert (sink.placed, sink.joined) == (placed, 4 * sum(LENGTHS) - placed)
    views = [n for n in SCHEMA.field_names if got.column(n)._values is not None and got.column(n)._values.base is not None]
    assert len(views) == ARRAYS[case]  # views of arrays with room behind them


def test_batches_that_yield_no_rows_give_an_empty_result():
    sink = BatchSink(SCHEMA, 9)
    rng = np.random.default_rng(0)
    for part in range(3):
        sink.append(_batch(rng, 0, part))
    assert_same_batch(sink.result(), ColumnBatch.empty(SCHEMA))
    assert (sink.rows, sink.placed, sink.joined) == (0, 0, 0)


def test_more_rows_than_the_sink_was_sized_for_is_an_error():
    sink = BatchSink(SCHEMA, 4)
    rng = np.random.default_rng(0)
    sink.append(_batch(rng, 3, 0))
    with pytest.raises(ValueError, match="sized for 4"):
        sink.append(_batch(rng, 2, 1))


# ---- through the read path -------------------------------------------------

BUCKETS, KEYS = 4, 3_000


def _commit(table, data, kinds=None):
    wb = table.new_batch_write_builder()
    w = wb.new_write()
    w.write(data, kinds=kinds)
    wb.new_commit().commit(w.prepare_commit())


def _rows(ids, r):
    ids = np.asarray(ids, dtype=np.int64)
    return {"id": ids, "c": (ids * 10 + r).tolist(), "d": (ids / 7 + r).tolist(), "s": [f"s{i % 11}-{r}" for i in ids]}


def _keyed(tmp_path, **options):
    """Four buckets of three overlapping runs each, left uncompacted, read split after split on the calling thread."""
    catalog = FileSystemCatalog(str(tmp_path / "warehouse"), commit_user="sink")
    table = catalog.create_table("db.t", SCHEMA, primary_keys=["id"], options={
        "bucket": str(BUCKETS), "write-only": "true", "scan.prefetch-splits": "0", **options})
    rng = np.random.default_rng(5)
    for r in range(3):
        _commit(table, _rows(np.sort(rng.choice(KEYS, 1_200, replace=False)), r))
    return table


def _ids_of_split(table, i):
    rb = table.new_read_builder()
    return list(rb.new_read().batches(rb.new_scan().plan()))[i].column("id").values


def _one_delete(table):
    _commit(table, _rows(_ids_of_split(table, 1)[:1], 9), kinds=["-D"])
    return {1}, {}


def _a_bucket_deleted(table):
    ids = _ids_of_split(table, 2)
    _commit(table, _rows(ids, 9), kinds=["-D"] * len(ids))
    return {2}, {}


def _nulls_in_one_split(table):
    ids = _ids_of_split(table, 3)[::2]
    _commit(table, {**_rows(ids, 9), "c": [None] * len(ids)})
    return set(), {}


def _append_table(tmp_path):
    catalog = FileSystemCatalog(str(tmp_path / "warehouse"), commit_user="sink")
    table = catalog.create_table("db.log", SCHEMA, options={"bucket": str(BUCKETS), "bucket-key": "id", "scan.prefetch-splits": "0"})
    for r in range(2):
        _commit(table, _rows(np.arange(r * 900, r * 900 + 900), r))
    return table


# case -> (table options (None: the append table), what is written after the three runs: it returns the splits
# whose batches are appended and the read builder's settings)
READ_CASES = {
    "all-splits-in-place": ({}, lambda t: (set(), {})),
    "a-split-with-a-deleted-winner": ({}, _one_delete),
    "a-predicate": ({}, lambda t: (set(range(BUCKETS)), {"filter": PredicateBuilder(SCHEMA).greater_than("c", 9_000)})),
    "a-projection": ({}, lambda t: (set(), {"projection": ["s", "d", "id"]})),
    "a-split-that-yields-no-rows": ({}, _a_bucket_deleted),
    "a-column-null-in-one-split-only": ({}, _nulls_in_one_split),
    "code-backed-strings": ({"merge.dict-domain": "true"}, lambda t: (set(), {})),
    "splits-finished-by-the-pipeline's-workers": ({"scan.prefetch-splits": "2"}, lambda t: (set(range(BUCKETS)), {})),
    "an-append-table": (None, lambda t: (set(range(BUCKETS)), {})),
}


@pytest.mark.parametrize("case", READ_CASES)
def test_read_all_gives_the_concatenation_of_the_split_batches(case, tmp_path):
    options, write = READ_CASES[case]
    table = _append_table(tmp_path) if options is None else _keyed(tmp_path, **options)
    appended, settings = write(table)

    def builder():
        rb = table.new_read_builder()
        if "filter" in settings:
            rb = rb.with_filter(settings["filter"])
        if "projection" in settings:
            rb = rb.with_projection(settings["projection"])
        return rb

    splits = builder().new_scan().plan()
    assert len(splits) == BUCKETS
    batches = list(builder().new_read().batches(splits))  # no sink: arrays of their own
    want = concat_batches(batches)
    before = dict(registry.snapshot().get("read", {}))
    got = builder().new_read().read_all(splits)
    counted = {k: v - before.get(k, 0) for k, v in registry.snapshot()["read"].items()}
    assert_same_batch(got, want)
    assert 0 < got.num_rows == counted["rows_out"]
    if case == "a-split-that-yields-no-rows":
        assert batches[2].num_rows == 0
    if case == "a-column-null-in-one-split-only":
        assert [b.column("c").null_count > 0 for b in batches] == [False, False, False, True]
        assert got.column("c").null_count == batches[3].column("c").null_count
    if case == "code-backed-strings":
        assert got.column("s").is_code_backed
    # in place: the fixed-width columns (all but s) of the splits that no one appends
    in_place = sum(b.num_rows for i, b in enumerate(batches) if i not in appended)
    assert counted["rows_placed"] == sum(n != "s" for n in got.schema.field_names) * in_place
    assert counted["rows_placed"] + counted["rows_joined"] == len(got.schema.fields) * got.num_rows


def test_a_single_split_is_returned_as_it_is(tmp_path):
    table = _keyed(tmp_path, bucket="1")
    rb = table.new_read_builder()
    before = dict(registry.snapshot().get("read", {}))
    got = rb.new_read().read_all(rb.new_scan().plan())
    counted = {k: v - before.get(k, 0) for k, v in registry.snapshot()["read"].items()}
    assert got.num_rows and got.column("id")._values.base is None  # the gather's own array
    assert (counted["rows_placed"], counted["rows_joined"]) == (0, 0)


def test_a_limit_reads_split_by_split_and_appends(tmp_path):
    table = _keyed(tmp_path)
    rb = table.new_read_builder().with_limit(1_000)
    splits = rb.new_scan().plan()
    before = dict(registry.snapshot().get("read", {}))
    got = rb.new_read().read_all(splits)
    counted = {k: v - before.get(k, 0) for k, v in registry.snapshot()["read"].items()}
    assert_same_batch(got, concat_batches(list(rb.new_read().batches(splits))))
    assert got.num_rows == 1_000 and (counted["rows_placed"], counted["rows_joined"]) == (0, 4_000)


@pytest.mark.parametrize("narrowed", ["a-filter", "a-limit", "neither"])
def test_a_read_that_returns_a_sliver_sizes_no_array_by_the_plan(narrowed, tmp_path, monkeypatch):
    """The plan's rows bound the result of a whole read, and are of its order; under a predicate or a limit
    they say nothing of it, and mapping them (8 B a row and column) is what a point query cannot afford."""
    made = []

    class Recorded(BatchSink):
        def __init__(self, schema, capacity):
            super().__init__(schema, capacity)
            made.append(self)

    monkeypatch.setattr(batch_module, "BatchSink", Recorded)
    table = _keyed(tmp_path)
    rb = table.new_read_builder()
    if narrowed == "a-filter":
        rb = rb.with_filter(PredicateBuilder(SCHEMA).less_than("c", 500))
    if narrowed == "a-limit":
        rb = rb.with_limit(10)
    splits = rb.new_scan().plan()
    got = rb.new_read().read_all(splits)
    assert_same_batch(got, concat_batches(list(rb.new_read().batches(splits))))
    (sink,) = made
    rows_in = sum(s.row_count for s in splits)
    views = [n for n in ("id", "c", "d") if got.column(n)._values.base is not None and len(got.column(n)._values.base) == rows_in]
    if narrowed == "neither":
        assert sink.capacity == rows_in > got.num_rows and views == ["id", "c", "d"]
        assert all(len(a) == rows_in for a in sink._arrays.values())
    else:
        assert 0 < got.num_rows < rows_in // 20
        assert sink.capacity is None and not sink._arrays and not views  # nothing of the plan's size behind the result


@pytest.mark.parametrize("role,name", [("value", "_seq"), ("key", "_seq"), ("value", "_kind")])
def test_a_column_named_like_a_task_of_the_gather_keeps_its_values(role, name, tmp_path):
    """The gather takes seq and kind beside the schema's columns and labels their spans "_seq" and "_kind";
    no schema reserves those names (the system columns are _SEQUENCE_NUMBER and _VALUE_KIND), and only a
    column of the schema may be written into the result."""
    key, value = (name, "c") if role == "key" else ("id", name)
    schema = pt.RowType.of((key, pt.BIGINT(False)), (value, pt.BIGINT()), ("d", pt.DOUBLE()), ("s", pt.STRING()))
    catalog = FileSystemCatalog(str(tmp_path / "warehouse"), commit_user="sink")
    table = catalog.create_table("db.t", schema, primary_keys=[key], options={
        "bucket": str(BUCKETS), "write-only": "true", "scan.prefetch-splits": "0"})
    rng = np.random.default_rng(5)
    for r in range(3):
        rows = _rows(np.sort(rng.choice(KEYS, 1_200, replace=False)), r)
        _commit(table, {key: rows["id"], value: rows["c"], "d": rows["d"], "s": rows["s"]})
    rb = table.new_read_builder()
    splits = rb.new_scan().plan()
    before = dict(registry.snapshot().get("read", {}))
    got = rb.new_read().read_all(splits)
    counted = {k: v - before.get(k, 0) for k, v in registry.snapshot()["read"].items()}
    assert_same_batch(got, concat_batches(list(rb.new_read().batches(splits))))
    ids, values = got.column(key).values, got.column(value).values
    assert np.array_equal(values // 10, ids) and set((values % 10).tolist()) == {0, 1, 2}  # id * 10 + run: no sequence number
    assert len(splits) == BUCKETS and counted["rows_placed"] == 3 * got.num_rows
