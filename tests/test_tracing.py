"""The program's spans and counters on the read path (docs/tracing.md).

A span is a jax.profiler.TraceAnnotation `pt:<name>`: it exists in a trace
exactly when a profiler session is open. The tests trace a tiny merge-read on
the CPU with the device engine pinned (conftest), read the trace back with
jax.profiler.ProfileData, and hold the names, the nesting, the operation id
and its way into the pool threads; the merge{...} counters against what the
shapes give; and the device-side names: one program name a jitted function,
and named scopes that add no instruction.
"""

import contextlib
import glob
import re
import threading

import jax
import numpy as np
import pytest

import paimon_tpu as pt
from paimon_tpu import metrics
from paimon_tpu.catalog import FileSystemCatalog
from paimon_tpu.metrics import Histogram, MetricRegistry, carried, registry, span
from paimon_tpu.ops import merge as M

RUNS, ROWS_A_RUN, KEYS = 4, 3_000, 10_000


@contextlib.contextmanager
def traced(directory):
    """A profiler session as an operator opens one; yields a list that holds,
    once the session has closed, every `pt:` event as (name, start_ns, end_ns,
    line, stats)."""
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


def _table(warehouse, string_key=False, **options):
    catalog = FileSystemCatalog(str(warehouse), commit_user="tracing")
    table = catalog.create_table(
        "db.t", pt.RowType.of(("id", pt.STRING(False) if string_key else pt.BIGINT(False)), ("v", pt.DOUBLE()),
                              ("s", pt.STRING())),
        primary_keys=["id"], options={"bucket": "1", "write-only": "true", **options})
    rng = np.random.default_rng(3)
    for r in range(RUNS):  # overlapping sorted runs, so the read has to merge
        ids = np.sort(rng.choice(KEYS, ROWS_A_RUN, replace=False)).astype(np.int64)
        wb = table.new_batch_write_builder()
        w = wb.new_write()
        keys = np.array([f"k{i:05d}" for i in ids], dtype=object) if string_key else ids
        w.write({"id": keys, "v": ids * 1.0 + r, "s": np.array([f"s{i % 7}" for i in ids], dtype=object)})
        wb.new_commit().commit(w.prepare_commit())
    return table


def _read(table):
    rb = table.new_read_builder()
    return rb.new_read().read_all(rb.new_scan().plan())


def _columns(batch):
    return {n: (np.asarray(batch.column(n).values).tolist(), batch.column(n).valid_mask().tolist())
            for n in batch.schema.field_names}


@pytest.fixture(scope="module")
def reads(tmp_path_factory):
    """One table read four times: untraced and cold (nothing recorded), in a
    session that sees nothing of it, then cold again for the trace (the cache
    dropped) and warm (every file a cache hit). And its twin under a STRING
    key, traced: an integer key's sort operands come packed out of
    `lanes.encode`, so only such a read re-packs a lane matrix
    (`lanes.compress`)."""
    tmp = tmp_path_factory.mktemp("tracing")
    table = _table(tmp / "warehouse")
    untraced = _columns(_read(table))
    with traced(tmp / "after") as after:
        pass
    from paimon_tpu.utils.cache import clear_all

    clear_all()
    with traced(tmp / "cold") as cold:
        cold_out = _columns(_read(table))
    with traced(tmp / "warm") as warm:
        _read(table)
    string_keyed = _table(tmp / "string_key_warehouse", string_key=True)
    with traced(tmp / "string_key") as string_key:
        _read(string_keyed)
    return {"untraced": untraced, "after": after, "cold": cold, "cold_out": cold_out, "warm": warm,
            "string_key": string_key}


def _events(reads, name):
    return reads["string_key" if name == "lanes.compress" else "cold"]


def _named(events, name):
    return [e for e in events if e[0] == name]


def _inside(inner, outer):
    return inner[3] == outer[3] and outer[1] <= inner[1] and inner[2] <= outer[2]


READ_PATH_SPANS = ("plan", "read_all", "split", "decode.keys", "decode.values", "decode.file", "concat",
                   "lanes.encode", "lanes.compress", "merge.dispatch", "merge.resolve", "gather", "gather.plan",
                   "gather.column", "finish")


@pytest.mark.parametrize("name", READ_PATH_SPANS)
def test_a_traced_read_opens_the_span(reads, name):
    assert _named(_events(reads, name), name), sorted({e[0] for e in _events(reads, name)})


def test_an_integer_key_comes_packed_out_of_lanes_encode(reads):
    assert not _named(reads["cold"], "lanes.compress")
    (encode,) = _named(reads["cold"], "lanes.encode")  # one BIGINT key, no sequence lanes
    assert encode[4]["packed"] == 1 and encode[4]["lanes"] == 1 and encode[4]["rows"] == RUNS * ROWS_A_RUN
    assert all("packed" not in e[4] for e in _named(reads["string_key"], "lanes.encode"))


def test_spans_of_one_read_share_one_operation_id(reads):
    (read_all,) = _named(reads["cold"], "read_all")
    op = read_all[4]["op"]
    assert op > 0 and read_all[4]["splits"] == 1
    assert read_all[4]["rows_in"] == RUNS * ROWS_A_RUN and 0 < read_all[4]["rows_out"] <= KEYS
    for name, _, _, _, stats in reads["cold"]:
        assert stats["op"] == (0 if name == "plan" else op), (name, stats)
    (warm,) = _named(reads["warm"], "read_all")
    assert warm[4]["op"] == op + 1  # a process-wide count


@pytest.mark.parametrize("name,parent", [
    ("split", "read_all"), ("decode.keys", "split"), ("decode.values", "split"), ("lanes.encode", "split"),
    ("merge.dispatch", "split"), ("lanes.compress", "merge.dispatch"), ("merge.resolve", "split"),
    ("gather", "split"), ("gather.plan", "gather"), ("finish", "split")])
def test_spans_nest_as_documented(reads, name, parent):
    events = _events(reads, name)
    (read_all,) = _named(events, "read_all")
    assert _named(events, name)
    for e in _named(events, name):
        assert e[4]["parent"] == parent
        assert any(_inside(e, p) for p in _named(events, parent)), (name, parent)
        assert _inside(e, read_all)
    # the reading thread's spans in their order: the dispatch comes before the
    # value pass (the device sorts while the host decodes), the resolve after it
    first = {n: min(e[1] for e in _named(reads["cold"], n)) for n in
             ("decode.keys", "merge.dispatch", "decode.values", "merge.resolve", "gather", "finish")}
    assert list(first) == sorted(first, key=first.get)


def test_concat_is_opened_under_split(reads):
    # one split: read_all returns its batch as it is, and joins nothing
    assert {e[4]["parent"] for e in _named(reads["cold"], "concat")} == {"split"}
    assert "parent" not in _named(reads["cold"], "read_all")[0][4]  # nothing caused it


def test_a_read_of_several_splits_opens_concat_under_read_all(tmp_path):
    table = _table(tmp_path / "warehouse", bucket="3")
    with traced(tmp_path / "trace") as events:
        out = _read(table)
    (read_all,) = _named(events, "read_all")
    joins = [e for e in _named(events, "concat") if e[4]["parent"] == "read_all"]
    # the result's arrays, a split's batch as it arrives, then what could not be sized beforehand (s), once
    assert [e[4]["rows"] for e in joins][0] == 0
    assert [e[4]["rows"] for e in joins][-1] == sum(e[4]["rows"] for e in joins[:-1]) == out.num_rows
    assert len(joins) == read_all[4]["splits"] + 2 == 5 and all(_inside(e, read_all) for e in joins)


def test_a_file_decoded_on_a_pool_thread_names_its_operation_and_who_asked(reads):
    (read_all,) = _named(reads["cold"], "read_all")
    files = _named(reads["cold"], "decode.file")
    assert len(files) == 2 * RUNS  # a key pass and a value pass over each run's file
    assert {f[4]["pass"] for f in files} == {"keys", "values"}
    assert {f[3] for f in files} != {read_all[3]}, "no decode left the reading thread"
    for f in files:
        assert f[4]["op"] == read_all[4]["op"]
        assert f[4]["parent"] == ("decode.keys" if f[4]["pass"] == "keys" else "decode.values")
        assert f[4]["format"] == "parquet" and f[4]["rows"] == ROWS_A_RUN and f[4]["bytes"] > 0
        assert f[4]["columns"] == (1 if f[4]["pass"] == "keys" else 2)
        waits = _named(reads["cold"], f[4]["parent"])
        assert any(w[1] <= f[1] and f[2] <= w[2] for w in waits)  # inside the reader's wait, on another line


def test_a_column_gathered_on_a_pool_thread_names_its_operation_and_the_gather(reads):
    (read_all,) = _named(reads["cold"], "read_all")
    (gather,) = _named(reads["cold"], "gather")
    columns = _named(reads["cold"], "gather.column")
    assert gather[4]["parts"] == RUNS and gather[4]["columns"] == 3
    # the value columns from their per-file parts, the key column, seq and kind whole
    assert {c[4]["column"]: c[4]["parts"] for c in columns} == {"id": 1, "v": RUNS, "s": RUNS, "_seq": 1, "_kind": 1}
    assert {c[3] for c in columns} != {read_all[3]}, "no column left the reading thread"
    for c in columns:
        assert c[4]["op"] == read_all[4]["op"] and c[4]["parent"] == "gather"
        assert c[4]["rows_out"] == gather[4]["rows_out"] == read_all[4]["rows_out"]
        assert gather[1] <= c[1] and c[2] <= gather[2]  # inside the reader's gather, on whichever line
    # the value pass (2 columns) is not concatenated: what is left joins the key pass and the sections
    assert sorted(c[4]["columns"] for c in _named(reads["cold"], "concat")) == [1, 3]


def test_a_cache_hit_opens_no_decode_file(reads):
    assert _named(reads["warm"], "decode.keys") and _named(reads["warm"], "decode.values")
    assert not _named(reads["warm"], "decode.file")


def test_without_a_session_nothing_is_recorded_and_the_output_is_the_same(reads):
    assert reads["after"] == []  # the untraced read before that session left nothing to find
    assert reads["untraced"] == reads["cold_out"]
    assert span.current() is None


def test_span_stats_sum_and_reach_the_innermost_open_span(tmp_path):
    with traced(tmp_path) as events:
        with span("outer", rows=5) as outer:
            assert span.current() is outer
            with span("inner"):
                span.current().add(tiles=2, bytes=10)
                span.current().add(tiles=1)
            outer.add(rows_out=4)
        assert span.current() is None
    (o,), (i,) = _named(events, "outer"), _named(events, "inner")
    assert o[4] == {"op": 0, "rows": 5, "rows_out": 4}
    assert i[4] == {"op": 0, "parent": "outer", "tiles": 3, "bytes": 10}


def test_a_span_records_its_wall_time_in_a_histogram_traced_or_not():
    h = Histogram()
    with span("timed", histogram=h):
        pass
    assert h.total == 1 and 0 <= h.sum == h.last < 1000


def test_carried_hands_operation_and_span_name_to_another_thread():
    seen = []

    def probe():
        with span("child") as child:
            seen.append((child.op, metrics._CURRENT.get()[:2]))
        seen.append(metrics._CURRENT.get())

    with span("asker", new_op=True) as asker:
        worker = threading.Thread(target=carried(probe))
        worker.start()
        worker.join(timeout=10)
        assert not worker.is_alive()
        bare = threading.Thread(target=probe)  # a ContextVar does not cross by itself
        bare.start()
        bare.join(timeout=10)
    assert seen[0] == (asker.op, (asker.op, "child")) and seen[1] == (asker.op, "asker", None)
    assert seen[2] == (0, (0, "child")) and seen[3] == (0, "", None)


# ---- the registry the readers lean on ---------------------------------------

def test_histogram_keeps_lifetime_total_and_sum_beside_its_window():
    h = Histogram(window=3)
    for v in range(10):
        h.update(float(v))
    assert (h.count, h.total, h.sum, h.max, h.last) == (3, 10, 45.0, 9.0, 9.0)
    r = MetricRegistry()
    r.group("g").histogram("ms", window=3).update(2.0)
    assert r.snapshot()["g"]["ms"] == {"count": 1, "mean": 2.0, "max": 2.0, "total": 1, "sum": 2.0}


def test_snapshot_holds_while_other_threads_add_groups():
    import sys

    r, stop, faults = MetricRegistry(), threading.Event(), []

    def add(worker):
        i = 0
        while not stop.is_set() and i < 500:
            r.group(f"g{worker}", n=str(i)).counter("c").inc()
            i += 1

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        workers = [threading.Thread(target=add, args=(w,)) for w in range(8)]
        for w in workers:
            w.start()
        try:
            while any(w.is_alive() for w in workers):
                r.snapshot()
        except RuntimeError as e:  # dictionary changed size during iteration
            faults.append(e)
        stop.set()
        for w in workers:
            w.join(timeout=30)
        assert not any(w.is_alive() for w in workers)
    finally:
        sys.setswitchinterval(old)
    assert faults == []
    assert sum(v["c"] for v in r.snapshot().values()) == len(r.groups)


# ---- merge{...} against what the shapes give ---------------------------------

@pytest.fixture
def merge_counters():
    registry.reset()
    yield lambda: registry.snapshot().get("merge", {})
    registry.reset()


def test_counters_of_a_merge_cut_into_three_tiles(merge_counters):
    lanes = np.concatenate([np.arange(1000) * 3 + r for r in range(3)]).astype(np.uint32)[:, None]
    handle = M.deduplicate_tiled_dispatch(lanes, [0, 1000, 2000, 3000], tile_rows=1000, compress=False)
    assert handle[0] == "batched"
    # tiles of 999, 1000 and 1001 rows share one pad bucket of 1024 and one
    # chunk of 4 (a power of two), their lanes narrowed to u16
    assert merge_counters() == {"merges": 1, "rows_in": 3000, "tiles": 3, "pad_rows": 4 * 1024 - 3000,
                                "h2d_bytes": 4 * 1024 * (2 + 1)}
    assert len(M.deduplicate_resolve_tiled(handle)) == 3000
    # the counts of the chunk's 4 slots (int64) and the chunk's packed indices whole (int32): an
    # array this small comes down as it is and is cut on the host, so no program follows the counts
    assert merge_counters()["d2h_bytes"] == 4 * 8 + 4 * 1024 * 4 and merge_counters()["winners"] == 3000


def test_counters_of_a_single_tile_merge(merge_counters):
    lanes = np.concatenate([np.arange(0, 240_000, 3), np.arange(0, 240_000, 6)]).astype(np.uint32)[:, None]
    n, m = 120_000, 131_072
    out = M.deduplicate_resolve(M.deduplicate_select_async(lanes, None, compress=False))
    assert len(out) == 80_000
    # one u32 lane (its range passes u16) and the u8 pad flag up; the count
    # (int64) and the packed int32 indices, whole, down
    assert merge_counters() == {"merges": 1, "rows_in": n, "tiles": 1, "pad_rows": m - n, "h2d_bytes": 4 * m + m,
                                "d2h_bytes": 8 + m * 4, "winners": 80_000}


def test_counters_of_a_merge_streamed_as_tiles_of_one_shape(merge_counters):
    lanes = np.concatenate([np.arange(0, 300_000, 3), np.arange(0, 300_000, 6)]).astype(np.uint32)[:, None]
    n, m = 150_000, M._STREAM_TILE_ROWS  # more rows than a tile: two key-range tiles, a call each
    handle = M.deduplicate_select_async(lanes, None, compress=False)
    assert handle[0] == "stream" and len(handle[1]) == 2
    assert len(M.deduplicate_resolve(handle)) == 100_000
    assert merge_counters() == {"merges": 1, "rows_in": n, "tiles": 2, "pad_rows": 2 * m - n,
                                "h2d_bytes": 2 * (4 * m + m), "d2h_bytes": 2 * (8 + m * 4), "winners": 100_000}


def test_counters_of_the_delta_packed_compact_variant(merge_counters):
    lanes = np.concatenate([np.arange(0, 300_000, 3), np.arange(0, 300_000, 6)]).astype(np.uint32)[:, None]
    n, m = 150_000, 262_144
    handle = M.deduplicate_tiled_dispatch(lanes, [0, 100_000, n], tile_rows=1 << 20, compress=False)
    assert handle[0][0][0] == "compact"  # conftest forces the link encodings on
    assert len(M.deduplicate_resolve_tiled(handle)) == 100_000
    # up: u16 deltas, the u8 pad flag, 4 run starts (i32) and bases (u32); down: the count, the
    # keep-mask (a bit a padded row) and the 2-bit run-ids (of every padded row), both whole
    assert merge_counters() == {"merges": 1, "rows_in": n, "tiles": 1, "pad_rows": m - n,
                                "h2d_bytes": 2 * m + m + 4 * 4 + 4 * 4,
                                "d2h_bytes": 8 + m // 8 + m // 4, "winners": 100_000}


def test_merge_plan_counts_too(merge_counters):
    lanes = np.arange(300, dtype=np.uint32)[::-1].copy()[:, None]
    plan = M.merge_plan(lanes, compress=False)
    assert plan.n == 300 and plan.m == 512
    c = merge_counters()
    assert (c["merges"], c["rows_in"], c["tiles"], c["pad_rows"]) == (1, 300, 1, 212)
    assert c["h2d_bytes"] == 512 * 4 + 0 + 512 * 4  # one u32 lane, no sequence lane, a u32 pad flag
    assert c["d2h_bytes"] == 512 * (4 + 1 + 1 + 4)  # perm, seg_start, keep_last, seg_id


def test_a_read_counts_rows_and_decodes(tmp_path):
    registry.reset()
    table = _table(tmp_path / "w")
    out = _read(table)
    snap = registry.snapshot()
    # 3 columns, seq and kind gathered; from the per-file parts only v, the numeric value column:
    # pyarrow joins the chunks of the arrow-backed s inside its take, so s is no column "from parts"
    # one split: nothing is written into a result of several, nothing is joined
    assert snap["read"] == {"ops": 1, "rows_in": RUNS * ROWS_A_RUN, "rows_out": out.num_rows,
                            "rows_gathered": 5 * out.num_rows, "rows_gathered_from_parts": out.num_rows,
                            "rows_placed": 0, "rows_joined": 0}
    assert snap["datafile"]["files_decoded"] == 2 * RUNS and snap["datafile"]["rows_decoded"] == 2 * RUNS * ROWS_A_RUN
    assert snap["datafile"]["bytes_decoded"] > 0
    assert snap["merge"]["merges"] == 1 and snap["merge"]["winners"] == out.num_rows
    assert snap["scan"]["plans"] >= 1 and snap["scan"]["duration_ms"]["total"] == snap["scan"]["plans"]
    _read(table)  # every file a cache hit
    assert registry.snapshot()["datafile"] == snap["datafile"]
    registry.reset()


# ---- names on the device ------------------------------------------------------

_M = 128
_U32, _U16, _U8 = (np.zeros(_M, dtype=d) for d in (np.uint32, np.uint16, np.uint8))
_I32, _BOOL = np.zeros(_M, dtype=np.int32), np.zeros(_M, dtype=np.bool_)
_STARTS, _BASES, _BASE = np.zeros(4, dtype=np.int32), np.zeros(4, dtype=np.uint32), np.zeros(1, dtype=np.uint32)
_FV = np.zeros((1, _M), dtype=np.bool_)

PROGRAMS = [
    ("merge_plan", lambda: M._plan_fn(1, 0), ([_U32], [], _U32)),
    ("merge_plan_ovc", lambda: M._plan_fn(1, 0, 8), ([_U32], [], _U32, _BASE)),
    ("dedup_select", lambda: M._dedup_select_fn(1, 0), ([_U32], [], _U8)),
    ("dedup_select_ovc", lambda: M._dedup_select_fn(1, 0, "xla", 8), ([_U32], [], _U8, _BASE)),
    ("dedup_select_compact", lambda: M._dedup_select_compact_fn(1, 0), ([_U32], [], _U8, _STARTS)),
    ("dedup_select_compact_ovc", lambda: M._dedup_select_compact_fn(1, 0, 8), ([_U32], [], _U8, _STARTS, _BASE)),
    ("dedup_select_delta", lambda: M._dedup_select_delta_fn(), (_U16, _STARTS, _BASES, _U8)),
    ("dedup_select_delta_wide", lambda: M._dedup_select_delta_wide_fn(), (_U16, _STARTS, _BASES, _U8)),
    ("dedup_select_batched", lambda: M._dedup_select_batched_fn(1), ((np.zeros((2, _M), np.uint32),), np.zeros((2, _M), np.uint8))),
    ("partial_update", lambda: M._partial_update_fn(), (_I32, _I32, _FV, _BOOL, _BOOL)),
    ("fused_partial_update_compact", lambda: M._fused_partial_update_compact_fn(1, 0, 1),
     ([_U32], [], _U8, _FV, _BOOL, _BOOL, _STARTS)),
    ("fused_partial_update", lambda: M._fused_partial_update_fn(1, 0, 1), ([_U32], [], _U8, _FV, _BOOL, _BOOL)),
]


@pytest.mark.parametrize("name,build,args", PROGRAMS, ids=[p[0] for p in PROGRAMS])
def test_each_jitted_function_lowers_to_a_module_of_its_own_name(name, build, args):
    assert f"module @jit_{name} " in build().lower(*args).as_text()


def test_every_jit_of_the_merge_module_is_named():
    import inspect

    source = inspect.getsource(M)
    assert "@jax.jit" not in source and source.count("@_jit(") == len(PROGRAMS)


_METADATA = re.compile(r",? ?metadata=\{[^}]*\}")


def _instructions(lowered) -> str:
    """The lowered HLO with each instruction's metadata (where a scope shows),
    without the tables of files and stack frames between the module's header
    and its computations."""
    header, _, rest = lowered.as_text(dialect="hlo", debug_info=True).partition("\nFileNames")
    return header + rest.partition("\nStackFrames")[2].partition("\n\n")[2]


_ARGS = {name: args for name, _, args in PROGRAMS}


@pytest.mark.parametrize("factory,arity,args", [
    (M._dedup_select_batched_fn, (1,), _ARGS["dedup_select_batched"]),
    (M._dedup_select_fn, (1, 0), _ARGS["dedup_select"]),
    (M._dedup_select_compact_fn, (1, 0), _ARGS["dedup_select_compact"]),
    (M._dedup_select_delta_fn, (), _ARGS["dedup_select_delta"]),
], ids=["batched", "select", "compact", "delta"])
def test_named_scopes_add_no_instruction(factory, arity, args, monkeypatch):
    fresh = factory.__wrapped__  # past the lru_cache: a new jitted function, traced anew
    scoped = _instructions(fresh(*arity).lower(*args))
    assert all(f"merge.{scope})/" in scoped or f"/merge.{scope}/" in scoped for scope in ("sort", "segment", "pack"))
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = _instructions(fresh(*arity).lower(*args))
    assert not re.search(r"merge\.(sort|segment|pack)", bare) and "metadata=" in bare
    assert _METADATA.sub("", scoped) == _METADATA.sub("", bare)
