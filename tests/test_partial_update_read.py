"""A wide partial-update table assembled from four sources, read with a key
range and a value filter through the Table API: each field of a key is the
latest non-null value written for it, and the value predicate is decided on
the merged row. The system (`ReadBuilder.with_filter` -> `plan()` ->
`read_all()`) against a plain numpy reference, under the host engine
(`sort-engine` numpy: the planned merge) and the device engine (`xla-segmented`: the
fused kernel and its index download); and the spans and counters
the partial-update path opens and counts (docs/tracing.md).
"""

import contextlib
import glob

import jax
import numpy as np
import pyarrow as pa
import pytest

import paimon_tpu as pt
from paimon_tpu.catalog import FileSystemCatalog
from paimon_tpu.data.batch import Column, ColumnBatch
from paimon_tpu.data.predicate import PredicateBuilder, and_
from paimon_tpu.metrics import registry

KEYS = 24_000
SOURCES = (  # (share of the keys, the columns the source writes), committed in this order
    (1.0, ("c1", "c2", "c3", "c4", "c5", "c6")),
    (0.75, ("d1", "d2", "d3", "d4")),
    (0.5, ("s1", "s2", "s3", "s4")),
    (0.25, ("c1", "c2", "c3")),
)
ROWS = sum(int(share * KEYS) for share, _ in SOURCES)  # 60,000
KEY_BOUND, C2_BOUND = 3 * 18_000, 49  # the key predicate keeps three quarters of the keys
NAMES = ["id"] + [f"c{j}" for j in range(1, 7)] + [f"d{j}" for j in range(1, 5)] + [f"s{j}" for j in range(1, 5)]
SCHEMA = pt.RowType.of(("id", pt.BIGINT(False)), *((n, pt.BIGINT()) for n in NAMES[1:7]),
                       *((n, pt.DOUBLE()) for n in NAMES[7:11]), *((n, pt.STRING()) for n in NAMES[11:]))
VOCAB = np.array([f"v{i:03d}" for i in range(300)], dtype=object)


def _value(name, ids, run):
    """Column `name` of source `run`'s rows for keys `ids`; c2 is the key's
    alone (id % 98), so the value predicate does not follow the source."""
    j = int(name[1:])
    if name == "c2":
        return ids % 98
    if name[0] == "c":
        return ids * 4 + run * 1_000_000 + j
    if name[0] == "d":
        return ids * 0.5 + run + j * 0.25
    return VOCAB[(ids * 7 + run * 13 + j) % 300]


def _sources(seed):
    rng = np.random.default_rng(seed)
    out = []
    for share, _ in SOURCES:
        count = int(share * KEYS)
        out.append(np.arange(KEYS) if count == KEYS else np.sort(rng.choice(KEYS, count, replace=False)))
    return out


def _ids(positions):
    return positions.astype(np.int64) * 3 + 1


def _null_fill(name):
    return "" if name[0] == "s" else 0


def _dtype(name):
    return object if name[0] == "s" else (np.float64 if name[0] == "d" else np.int64)


def _batch(positions, run, columns) -> ColumnBatch:
    ids = _ids(positions)
    cols = {"id": Column(ids)}
    for name in NAMES[1:]:
        if name in columns:
            cols[name] = Column(np.asarray(_value(name, ids, run)))
        else:  # a column the source does not write: null
            cols[name] = Column(np.full(len(ids), None if name[0] == "s" else 0, dtype=_dtype(name)),
                                np.zeros(len(ids), dtype=np.bool_))
    return ColumnBatch(SCHEMA, cols)


def _expected(members):
    """name -> (values, validity) of the rows that pass the filter: each field
    from the last source that wrote it, null where none did."""
    values = {n: np.full(KEYS, _null_fill(n), dtype=_dtype(n)) for n in NAMES[1:]}
    valid = {n: np.zeros(KEYS, dtype=np.bool_) for n in NAMES[1:]}
    seen = np.zeros(KEYS, dtype=np.bool_)
    for run, (positions, (_, columns)) in enumerate(zip(members, SOURCES)):
        seen[positions] = True
        for name in columns:
            values[name][positions] = _value(name, _ids(positions), run)
            valid[name][positions] = True
    ids = _ids(np.arange(KEYS))
    keep = seen & (ids < KEY_BOUND) & valid["c2"] & (values["c2"] < C2_BOUND)
    out = {"id": (ids[keep], np.ones(int(keep.sum()), dtype=np.bool_))}
    out.update({n: (values[n][keep], valid[n][keep]) for n in NAMES[1:]})
    return out


def _table(warehouse, seed, **options):
    catalog = FileSystemCatalog(str(warehouse), commit_user="partial-update")
    table = catalog.create_table("db.wide", SCHEMA, primary_keys=["id"], options={
        "bucket": "1", "file.format": "orc", "write-only": "true", "merge-engine": "partial-update", **options})
    members = _sources(seed)
    for run, (positions, (_, columns)) in enumerate(zip(members, SOURCES)):
        wb = table.new_batch_write_builder()
        w = wb.new_write()
        w.write(_batch(positions, run, columns))
        wb.new_commit().commit(w.prepare_commit())
    return table, members


def _read(table):
    pb = PredicateBuilder(SCHEMA)
    rb = table.new_read_builder().with_filter(and_(pb.less_than("id", KEY_BOUND), pb.less_than("c2", C2_BOUND)))
    return rb.new_read().read_all(rb.new_scan().plan())


def _assert_matches(out, want):
    assert out.num_rows == len(want["id"][0])
    order = np.argsort(np.asarray(out.column("id").values), kind="stable")
    for name in NAMES:
        col = out.column(name)
        valid = col.valid_mask()[order]
        assert np.array_equal(valid, want[name][1]), name
        got = np.asarray(col.values)[order]
        assert np.array_equal(got[valid], want[name][0][valid]), name


ENGINES = {"numpy": {"sort-engine": "numpy"}, "xla": {"sort-engine": "xla-segmented"}}


@pytest.mark.parametrize("case", sorted(ENGINES))
def test_a_filtered_read_of_four_sources_merges_field_by_field(case, tmp_path):
    table, members = _table(tmp_path, seed=11, **ENGINES[case])
    registry.reset()
    out = _read(table)
    want = _expected(members)
    assert 8_000 < out.num_rows < 10_000  # about half of the 18,000 keys under the key bound
    _assert_matches(out, want)
    counted = registry.snapshot()["partial_update"]
    # every key of the files read merges, the filter comes after: 24,000 keys from 60,000 rows, 14 fields
    assert counted == {"merges": 1, "rows_in": ROWS, "rows_out": KEYS, "fields": len(NAMES) - 1}
    if case == "xla":  # one call of the fused kernel over the whole section
        merged = registry.snapshot()["merge"]
        assert (merged["merges"], merged["rows_in"], merged["tiles"], merged["pad_rows"]) == (1, ROWS, 1, 65_536 - ROWS)


@pytest.mark.parametrize("case", sorted(ENGINES))
def test_the_merge_takes_its_strings_in_arrow(case, tmp_path):
    """The four STRING fields arrive arrow-backed from the ORC reader and the
    merge takes each one's winners in arrow (dict{rows_taken_in_arrow}, a row
    a merged key): no column is turned into Python strings before the filter
    (dict{arrow_expanded})."""
    table, members = _table(tmp_path, seed=17, **ENGINES[case])
    registry.reset()
    out = _read(table)
    counted = registry.snapshot()["dict"]
    assert counted["rows_taken_in_arrow"] == 4 * KEYS
    assert counted.get("arrow_expanded", 0) == 0
    assert all(out.column(n)._values is None and out.column(n).arrow is not None for n in NAMES[11:])
    _assert_matches(out, _expected(members))


def test_a_source_that_leaves_every_string_null_writes_and_reads_back_as_nulls(tmp_path):
    table, members = _table(tmp_path, seed=5)
    rb = table.new_read_builder()
    out = rb.new_read().read_all(rb.new_scan().plan())
    assert out.num_rows == KEYS
    valid = out.column("s1").valid_mask()
    assert valid.sum() == len(members[2]) and not valid.all()
    typed = ColumnBatch(SCHEMA, _batch(np.arange(4), 0, ("c1",)).columns).to_arrow()
    assert typed.schema.field("s1").type == pa.string() and typed.column("s1").null_count == 4


@contextlib.contextmanager
def _traced(directory):
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 1
    options.python_tracer_level = 0
    events = []
    jax.profiler.start_trace(str(directory), profiler_options=options)
    try:
        yield events
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(directory / "plugins" / "profile" / "*" / "*.xplane.pb")))[-1]
    for pi, plane in enumerate(jax.profiler.ProfileData.from_file(path).planes):
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("pt:"):
                    events.append((e.name[3:], e.start_ns, e.start_ns + e.duration_ns, (pi, li), dict(e.stats)))


@pytest.fixture(scope="module")
def traced_read(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("pu_traced")
    table, members = _table(tmp / "warehouse", seed=13, **{"sort-engine": "xla-segmented"})
    with _traced(tmp / "trace") as events:
        out = _read(table)
    _assert_matches(out, _expected(members))
    return events


@pytest.mark.parametrize("name,parents", [
    ("decode.all", {"split"}), ("read.join", {"split"}), ("lanes.encode", {"split"}),
    ("pu.valid", {"split", "merge.dispatch"}), ("merge.dispatch", {"split"}), ("lanes.compress", {"merge.dispatch"}),
    ("merge.resolve", {"split"}), ("pu.unpack", {"split"}), ("pu.gather", {"split"}), ("finish", {"split"})])
def test_the_partial_update_path_opens_its_spans_on_the_reading_thread(traced_read, name, parents):
    (read_all,) = [e for e in traced_read if e[0] == "read_all"]
    mine = [e for e in traced_read if e[0] == name]
    assert mine and {e[4]["parent"] for e in mine} == parents
    assert all(e[3] == read_all[3] and e[4]["op"] == read_all[4]["op"] and read_all[1] <= e[1] and e[2] <= read_all[2]
               for e in mine)


def test_the_partial_update_spans_carry_their_stats(traced_read):
    by_name = {}
    for e in traced_read:
        by_name.setdefault(e[0], []).append(e[4])
    fields = len(NAMES) - 1
    # the fields' validity stacked (the row kinds checked beside it), then padded inside the dispatch
    assert sorted(s["rows"] for s in by_name["pu.valid"]) == [ROWS, ROWS]
    assert all(s["fields"] == fields for s in by_name["pu.valid"])
    (unpack,), (gather,), (join,) = by_name["pu.unpack"], by_name["pu.gather"], by_name["read.join"]
    assert unpack == {**unpack, "rows_out": KEYS, "fields": fields} and "compact" not in unpack  # one download
    assert gather == {**gather, "rows_in": ROWS, "rows_out": KEYS, "fields": fields}
    assert join == {**join, "rows": ROWS, "columns": len(NAMES)}
    assert "concat" not in {e[0] for e in traced_read if e[4].get("parent") == "split" and e[4].get("rows") == ROWS}
