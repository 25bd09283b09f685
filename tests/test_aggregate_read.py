"""A sales rollup kept by the aggregation merge engine, read whole through the
Table API: six BIGINT columns summed, four DOUBLE columns maximised and four
STRING columns left to the documented default, `last_non_null_value`, over
four sources that each write every column with a tenth of the cells null.
The system (`plan()` -> `read_all()`) against a plain numpy reference on both
routes of `MergeExecutor.merge_async`: the fused kernel, which the CPU takes
by itself, and the planned route (`merge_plan`, then a call a field), which a
TPU takes because it has no exact f64 (forced here by making
`_f64_on_device_unsupported` true); and the spans and counters the
aggregation opens and counts on both (docs/tracing.md).
"""

import contextlib
import glob

import jax
import numpy as np
import pytest

import paimon_tpu as pt
from paimon_tpu.catalog import FileSystemCatalog
from paimon_tpu.data.batch import Column, ColumnBatch
from paimon_tpu.metrics import registry
from paimon_tpu.ops import aggregates

KEYS = 24_000
SHARES = (1.0, 0.75, 0.5, 0.25)  # each source's share of the keys, committed in this order
ROWS = sum(int(share * KEYS) for share in SHARES)  # 60,000
SUMS, MAXES, PICKS = [f"c{j}" for j in range(1, 7)], [f"d{j}" for j in range(1, 5)], [f"s{j}" for j in range(1, 5)]
NAMES = ["id"] + SUMS + MAXES + PICKS
SCHEMA = pt.RowType.of(("id", pt.BIGINT(False)), *((n, pt.BIGINT()) for n in SUMS),
                       *((n, pt.DOUBLE()) for n in MAXES), *((n, pt.STRING()) for n in PICKS))
OPTIONS = {"bucket": "1", "file.format": "orc", "write-only": "true", "merge-engine": "aggregation",
           **{f"fields.{n}.aggregate-function": "sum" for n in SUMS},
           **{f"fields.{n}.aggregate-function": "max" for n in MAXES}}
VOCAB = np.array([f"v{i:03d}" for i in range(300)], dtype=object)
NULL_SHARE = 0.1


def _hash(ids, run, j):
    x = ids.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15) + np.uint64(run * 1_000_003 + j * 7_919 + 1)
    x ^= x >> np.uint64(29)
    x *= np.uint64(0xBF58476D1CE4E5B9)
    return x ^ (x >> np.uint64(32))


def _value(name, ids, run):
    """Column `name` of source `run`'s rows: sums of about 3e9 an input, so a
    key's total passes 2**32 where two inputs are valid, c4 with either sign;
    maxima near 1e9 in quarters (no float32 holds them), in no order of the
    runs; strings that change with the run."""
    j = int(name[1:])
    h = _hash(ids, run, j)
    if name[0] == "c":
        v = (h % np.uint64(1 << 31)).astype(np.int64) + (1 << 31)
        return np.where(h & np.uint64(1 << 40), -v, v) if name == "c4" else v
    if name[0] == "d":
        return 1e9 + (h % np.uint64(1_000_000)).astype(np.float64) + 0.25 * ((h >> np.uint64(20)) % np.uint64(4)).astype(np.float64)
    return VOCAB[(h % np.uint64(300)).astype(np.int64)]


def _ids(positions):
    return positions.astype(np.int64) * 3 + 1


def _sources(seed):
    """Per source, the positions of its keys and each column's validity: a
    seeded tenth of the cells null, and every cell of the keys id % 97 == 1
    null in every source (keys with nothing but nulls)."""
    rng = np.random.default_rng(seed)
    out = []
    for share in SHARES:
        count = int(share * KEYS)
        positions = np.arange(KEYS) if count == KEYS else np.sort(rng.choice(KEYS, count, replace=False))
        empty = _ids(positions) % 97 == 1
        out.append((positions, {n: (rng.random(count) >= NULL_SHARE) & ~empty for n in NAMES[1:]}))
    return out


def _batch(positions, run, valid) -> ColumnBatch:
    ids = _ids(positions)
    cols = {"id": Column(ids)}
    for name in NAMES[1:]:
        values = np.asarray(_value(name, ids, run))
        if name[0] == "s":
            values = values.copy()
            values[~valid[name]] = None
        cols[name] = Column(values, valid[name])
    return ColumnBatch(SCHEMA, cols)


def _expected(sources):
    """name -> (values, validity) for every key: the int64 sum and the
    maximum of the non-null inputs, the non-null string of the highest
    sequence number, null where no input was."""
    ids = _ids(np.arange(KEYS))
    values = {n: np.zeros(KEYS, dtype=np.int64) for n in SUMS}
    values.update({n: np.full(KEYS, -np.inf) for n in MAXES})
    values.update({n: np.full(KEYS, "", dtype=object) for n in PICKS})
    valid = {n: np.zeros(KEYS, dtype=np.bool_) for n in NAMES[1:]}
    for run, (positions, ok) in enumerate(sources):
        for name in NAMES[1:]:
            at, v = positions[ok[name]], _value(name, ids[positions[ok[name]]], run)
            if name in SUMS:
                values[name][at] += v
            elif name in MAXES:
                values[name][at] = np.maximum(values[name][at], v)
            else:
                values[name][at] = v
            valid[name][at] = True
    out = {"id": (ids, np.ones(KEYS, dtype=np.bool_))}
    out.update({n: (values[n], valid[n]) for n in NAMES[1:]})
    return out


def _table(warehouse, seed):
    catalog = FileSystemCatalog(str(warehouse), commit_user="rollup")
    table = catalog.create_table("db.rollup", SCHEMA, primary_keys=["id"], options=OPTIONS)
    sources = _sources(seed)
    for run, (positions, valid) in enumerate(sources):
        wb = table.new_batch_write_builder()
        w = wb.new_write()
        w.write(_batch(positions, run, valid))
        wb.new_commit().commit(w.prepare_commit())
    return table, sources


def _read(table):
    rb = table.new_read_builder()
    return rb.new_read().read_all(rb.new_scan().plan())


def _assert_matches(out, want):
    assert out.num_rows == KEYS
    order = np.argsort(np.asarray(out.column("id").values), kind="stable")
    for name in NAMES:
        col = out.column(name)
        valid = col.valid_mask()[order]
        assert np.array_equal(valid, want[name][1]), name
        got = np.asarray(col.values)[order]
        assert got.dtype == (want[name][0].dtype if name[0] != "d" else np.float64), name
        assert np.array_equal(got[valid], want[name][0][valid]), name


@contextlib.contextmanager
def _route(name):
    """`planned`: the backend reports no exact f64, as a TPU does, so the
    DOUBLE max sends the merge to `merge_plan` and a call a field."""
    with pytest.MonkeyPatch.context() as mp:
        if name == "planned":
            mp.setattr(aggregates, "_f64_on_device_unsupported", lambda: True)
        yield


def test_the_values_make_every_shortcut_visible():
    sources = _sources(3)
    want = _expected(sources)
    c1, c4, d1, s1 = (want[n] for n in ("c1", "c4", "d1", "s1"))
    assert (c1[0][c1[1]] > 2**32).mean() > 0.6 and (c4[0][c4[1]] < 0).any()
    assert not np.array_equal(d1[0][d1[1]].astype(np.float32).astype(np.float64), d1[0][d1[1]])
    all_null = np.ones(KEYS, dtype=np.bool_)
    for n in NAMES[1:]:
        all_null &= ~want[n][1]
    assert 200 < all_null.sum() < 400  # id % 97 == 1, and no other key
    # the last source's maximum is not the key's maximum in many keys; its string is null in some
    positions, ok = sources[-1]
    last_d1 = np.where(ok["d1"], _value("d1", _ids(positions), 3), -np.inf)
    assert 0.2 < (last_d1 < d1[0][positions]).mean() < 0.8
    assert (~ok["s1"] & s1[1][positions]).sum() > 100


@pytest.mark.parametrize("route", ["fused", "planned"])
def test_a_rollup_of_four_sources_sums_maximises_and_keeps_the_last_non_null(route, tmp_path):
    with _route(route):
        table, sources = _table(tmp_path, seed=11)
        registry.reset()
        out = _read(table)
    _assert_matches(out, _expected(sources))
    counted = registry.snapshot()["aggregate"]
    on_host = len(MAXES) if route == "planned" else 0
    assert counted == {"merges": 1, "rows_in": ROWS, "rows_out": KEYS, "fields": len(NAMES) - 1,
                       "fields_on_host": on_host, "fields_by_scan": len(SUMS) + len(PICKS)}


@pytest.mark.parametrize("route", ["fused", "planned"])
def test_the_picks_take_their_strings_in_arrow(route, tmp_path):
    """The ORC reader hands the STRING columns over arrow-backed; the four
    `last_non_null_value` picks take their winners in arrow
    (dict{rows_taken_in_arrow}), and no column of the read is turned into
    Python strings (dict{arrow_expanded})."""
    with _route(route):
        table, sources = _table(tmp_path, seed=17)
        registry.reset()
        out = _read(table)
    counted = registry.snapshot()["dict"]
    assert counted["rows_taken_in_arrow"] == len(PICKS) * KEYS
    assert counted.get("arrow_expanded", 0) == 0
    assert all(out.column(n)._values is None and out.column(n).arrow is not None for n in PICKS)
    _assert_matches(out, _expected(sources))


@pytest.mark.parametrize("route", ["fused", "planned"])
def test_the_fields_reduced_by_scan_are_counted(route, tmp_path):
    """aggregate{fields_by_scan}: an integer sum, a count and a pick read
    scans at the segments' bounds; a FLOAT sum and a DOUBLE max do not."""
    schema = pt.RowType.of(("id", pt.BIGINT(False)), ("total", pt.BIGINT()), ("n", pt.INT()), ("f", pt.FLOAT()),
                           ("d", pt.DOUBLE()), ("s", pt.STRING()))
    options = {"bucket": "1", "write-only": "true", "merge-engine": "aggregation",
               "fields.total.aggregate-function": "sum", "fields.n.aggregate-function": "count",
               "fields.f.aggregate-function": "sum", "fields.d.aggregate-function": "max"}
    with _route(route):
        table = FileSystemCatalog(str(tmp_path), commit_user="scan").create_table(
            "db.scan", schema, primary_keys=["id"], options=options)
        for run in range(2):
            wb = table.new_batch_write_builder()
            w = wb.new_write()
            w.write({"id": [1, 2, 3], "total": [5, None, -7], "n": [1, 2, None], "f": [0.5, 1.5, None],
                     "d": [1.0, None, 3.0], "s": ["a", f"b{run}", None]})
            wb.new_commit().commit(w.prepare_commit())
        registry.reset()
        out = _read(table)
    # each flush stores a count, 0 where its rows were null, and the read counts the stored rows
    assert out.to_pylist() == [(1, 10, 2, 1.0, 1.0, "a"), (2, None, 2, 3.0, None, "b1"), (3, -14, 2, None, 3.0, None)]
    counted = registry.snapshot()["aggregate"]
    assert counted["fields"] == 5 and counted["fields_by_scan"] == 3
    assert counted["fields_on_host"] == (1 if route == "planned" else 0)


def test_every_flush_of_an_aggregation_table_counts(tmp_path):
    registry.reset()
    _table(tmp_path, seed=5)
    counted = registry.snapshot()["aggregate"]
    # one flush a source: its keys once each
    assert counted == {"merges": 4, "rows_in": ROWS, "rows_out": ROWS, "fields": 4 * (len(NAMES) - 1),
                       "fields_on_host": 0, "fields_by_scan": 4 * (len(SUMS) + len(PICKS))}


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


@pytest.mark.parametrize("route", ["fused", "planned"])
def test_the_aggregation_opens_its_spans_with_their_stats(route, tmp_path):
    with _route(route):
        table, sources = _table(tmp_path / "warehouse", seed=13)
        with _traced(tmp_path / "trace") as events:
            out = _read(table)
    _assert_matches(out, _expected(sources))
    (read_all,) = [e for e in events if e[0] == "read_all"]
    (reduce,) = [e for e in events if e[0] == "agg.reduce"]
    fields = [e for e in events if e[0] == "agg.field"]
    assert reduce[4] == {**reduce[4], "rows_in": ROWS, "rows_out": KEYS, "fields": len(NAMES) - 1, "route": route,
                         "parent": "split", "op": read_all[4]["op"]}
    assert reduce[3] == read_all[3] and read_all[1] <= reduce[1] and reduce[2] <= read_all[2]
    assert [e[4]["field"] for e in fields] == NAMES[1:]
    assert all(e[3] == reduce[3] and reduce[1] <= e[1] and e[2] <= reduce[2] and e[4]["parent"] == "agg.reduce"
               for e in fields)
    want_fn = {**dict.fromkeys(SUMS, "sum"), **dict.fromkeys(MAXES, "max"), **dict.fromkeys(PICKS, "last_non_null_value")}
    want_on = {**dict.fromkeys(SUMS, "device"), **dict.fromkeys(PICKS, "pick"),
               **dict.fromkeys(MAXES, "host" if route == "planned" else "device")}
    assert {e[4]["field"]: e[4]["fn"] for e in fields} == want_fn
    assert {e[4]["field"]: e[4]["on"] for e in fields} == want_on
    # the planned route sorts first, outside the reduction; the fused kernel sorts inside it
    dispatch = [e for e in events if e[0] == "merge.dispatch"]
    assert bool(dispatch) == (route == "planned")
    assert all(e[2] <= reduce[1] for e in dispatch)


@pytest.mark.parametrize("program", ["agg_sum", "agg_minmax", "agg_pick", "fused_aggregate"])
def test_the_aggregation_programs_have_names_of_their_own(program):
    m = 256
    perm, seg = np.arange(m, dtype=np.int32), np.arange(m, dtype=np.int32) // 2
    ends = np.arange(1, m, 2, dtype=np.int32)
    values, valid = np.arange(m, dtype=np.int64), np.ones(m, dtype=np.bool_)
    if program == "agg_sum":
        lowered = aggregates._sum_fn().lower(perm, ends, None, values, valid, np.ones(m, dtype=np.int64))
    elif program == "agg_minmax":
        lowered = aggregates._minmax_fn(True).lower(perm, ends, seg, values, valid)
    elif program == "agg_pick":
        lowered = aggregates._pick_fn(True).lower(perm, ends, valid)
    else:
        lanes = np.zeros((1, m), dtype=np.uint32)
        lowered = aggregates._fused_aggregate_fn(1, 0, ("sum",), "xla").lower(
            lanes, np.zeros((0, m), dtype=np.uint32), np.zeros(m, dtype=np.uint32), (values,), (valid,),
            (np.ones(m, dtype=np.int8),))
    assert f"module @jit_{program} " in lowered.as_text()  # the trace's XLA Modules line and its device events
