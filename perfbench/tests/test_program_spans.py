"""program_spans.py, the reduction from the program's `pt:` spans to the
per-layer numbers, on hand-made spans where every answer is known; the loader
on a small trace the profiler wrote; and the readers' rule for nothing to
read (None) against nothing counted (0)."""

import json
import os
import threading
import types

import pytest

import check_manifest
import program_spans
import run
import trace_reader
from program_spans import ProgramSpans, Span

READER, POOL = (0, 0), (0, 1)

NEW_METRICS = {
    "winner_share", "concat_ms_p50", "read_unattributed_share", "files_per_plan", "decode_wait_ms_p50",
    "decode_busy_ms_p50", "decoded_bytes_per_row", "lane_encode_ms_p50", "dispatch_ms_p50", "resolve_wait_ms_p50",
    "gather_ms_p50", "h2d_bytes_per_row", "d2h_bytes_per_row", "tiles_per_merge", "pad_row_share",
    "sort_busy_ms_per_mrow", "nonsort_busy_ms_per_mrow", "idle_attributed_share"}


def s(name, start, end, op, line=READER, **stats):
    return Span(name, start, end, line, {"op": op, **stats})


def one_operation(op, t0, line=READER):
    """read_all of 10 s: a split of 9 s holding decode.keys 2 s (two files of
    1.5 s each on a pool thread), merge.dispatch 2 s with lanes.compress 0.5 s
    inside, merge.resolve 1 s and gather 3 s; then a concat of 0.5 s."""
    return [
        s("read_all", t0, t0 + 10, op, line),
        s("split", t0 + 0.25, t0 + 9.25, op, line, parent="read_all"),
        s("decode.keys", t0 + 0.5, t0 + 2.5, op, line, parent="split"),
        s("decode.file", t0 + 0.5, t0 + 2.0, op, POOL, parent="decode.keys"),
        s("decode.file", t0 + 1.0, t0 + 2.5, op, (0, 2), parent="decode.keys"),
        s("merge.dispatch", t0 + 3, t0 + 5, op, line, parent="split"),
        s("lanes.compress", t0 + 3.5, t0 + 4, op, line, parent="merge.dispatch"),
        s("merge.resolve", t0 + 5, t0 + 6, op, line, parent="split"),
        s("gather", t0 + 6, t0 + 9, op, line, parent="split"),
        s("concat", t0 + 9.25, t0 + 9.75, op, line, parent="read_all"),
    ]


def test_self_time_is_length_less_children():
    spans = ProgramSpans(one_operation(7, 100.0))
    own = {sp.name: sp.self_s for sp in spans.spans if sp.line == READER}
    assert own == {"read_all": 0.5, "split": 1.0, "decode.keys": 2.0, "merge.dispatch": 1.5, "lanes.compress": 0.5,
                   "merge.resolve": 1.0, "gather": 3.0, "concat": 0.5}
    assert sum(own.values()) == 10.0  # self times add up to the operation
    assert spans.self_ms(("merge.dispatch",)) == [1500.0]
    assert spans.self_ms(("lanes",)) == [500.0]  # a family: lanes.encode and lanes.compress
    assert spans.self_ms(("merge",)) == [2500.0] and spans.self_ms(("nothing",)) == [0.0]


def test_spans_of_other_threads_are_joined_by_op():
    spans = ProgramSpans(one_operation(7, 100.0) + one_operation(8, 200.0) + [s("decode.file", 50, 60, 99, POOL)])
    assert spans.busy_ms(("decode.file",)) == [3000.0, 3000.0]  # two files of 1.5 s an operation, whatever the thread
    assert spans.self_ms(("decode",)) == [2000.0, 2000.0]  # the reader's own wait: the pool's files are not its children
    assert spans.reader_lines == {READER}


def test_operations_begun_outside_the_window_are_left_out():
    spans = one_operation(1, 0.0) + one_operation(2, 20.0) + one_operation(3, 40.0)
    inside = ProgramSpans(spans, window=(15.0, 45.0))
    assert [o.op for o in inside.operations] == [2, 3]  # 3 began inside and ends after it: an operation in flight counts
    assert len(ProgramSpans(spans).operations) == 3  # no pb:window in the trace: every operation
    assert len(inside.self_ms(("gather",))) == 2
    assert {name for name, *_ in inside.table()[:2]} == {"gather", "decode.file"}  # 6 s of self time each, the longest
    assert dict((n, c) for n, c, _, _ in inside.table())["decode.file"] == 4


def test_unattributed_share_is_the_containers_own_time():
    assert ProgramSpans(one_operation(7, 100.0)).unattributed_share() == pytest.approx((0.5 + 1.0) / 10)
    assert ProgramSpans([]).unattributed_share() is None
    assert ProgramSpans([s("plan", 0, 1, 0)]).unattributed_share() is None  # no read_all: not this program's spans


def test_idle_attributed_share_on_a_hand_made_case():
    spans = ProgramSpans(one_operation(7, 100.0) + [s("plan", 99.0, 99.5, 0)], window=(98.0, 112.0))
    # the device runs from 103 to 104 (under merge.dispatch) and from 110.5 to 111
    busy, window = [(103.0, 104.0), (110.5, 111.0)], (98.0, 112.0)
    assert program_spans.idle_gaps(busy, window) == [(98.0, 103.0), (104.0, 110.5), (111.0, 112.0)]
    # leaves: plan 99-99.5, decode.keys 100.5-102.5, dispatch 103-105, resolve 105-106, gather 106-109, concat 109.25-109.75
    assert spans.leaf_intervals() == [(99.0, 99.5), (100.5, 102.5), (103.0, 109.0), (109.25, 109.75)]
    attributed = 0.5 + 2.0 + (109.0 - 104.0) + 0.5
    assert spans.idle_attributed_share(busy, window) == pytest.approx(attributed / 12.5)
    assert spans.innermost_at(103.75) == "lanes.compress" and spans.innermost_at(100.3) == "split"
    assert spans.innermost_at(98.5) == "outside-read_all"
    assert ProgramSpans([]).idle_attributed_share(busy, window) is None
    assert program_spans.overlap([(0, 2), (3, 5)], [(1, 4)]) == 2


def _window(before, after, **more):
    return types.SimpleNamespace(counters_before=before, counters_after=after, **more)


def test_a_count_of_zero_over_a_base_is_zero_and_no_base_is_nothing():
    read = lambda name: run.load_module("layer_metrics", name).read  # noqa: E731
    parent = _window({"scan": {"plans": 3, "resulted_table_files": 12}}, {"scan": {"plans": 5, "resulted_table_files": 20}},
                     rows_per_op=100)
    assert read("files_per_plan")(parent) == 4.0
    for name in ("winner_share", "decoded_bytes_per_row", "h2d_bytes_per_row", "d2h_bytes_per_row", "tiles_per_merge",
                 "pad_row_share"):
        assert read(name)(parent) is None  # a program without these counters: the metric is left out
    change = _window(
        {"read": {"ops": 2, "rows_in": 200, "rows_out": 150}, "merge": {"merges": 2, "rows_in": 200, "tiles": 2, "pad_rows": 56,
                                                                          "h2d_bytes": 640, "d2h_bytes": 600}},
        {"read": {"ops": 5, "rows_in": 500, "rows_out": 400}, "merge": {"merges": 5, "rows_in": 500, "tiles": 11, "pad_rows": 256,
                                                                          "h2d_bytes": 2140, "d2h_bytes": 1800}},
        rows_per_op=100)
    assert read("winner_share")(change) == pytest.approx(250 / 300)
    assert read("decoded_bytes_per_row")(change) == 0  # every read hit the cache: no datafile{...} group yet
    assert read("h2d_bytes_per_row")(change) == 5.0 and read("d2h_bytes_per_row")(change) == 4.0
    assert read("tiles_per_merge")(change) == 3.0 and read("pad_row_share")(change) == 0.4
    with pytest.raises(ValueError, match="not the cell's 90 rows"):
        read("winner_share")(_window(change.counters_before, change.counters_after, rows_per_op=90))


def test_sort_time_is_told_from_the_rest_by_program_and_instruction():
    ops = [(0.0, 2.0, "jit_dedup_select_batched/sort.12:u32[2,8],s32[2,8]"), (2.0, 3.0, "jit_dedup_select_batched/sort:u8[2,8]"),
           (3.0, 3.5, "jit_dedup_select_delta/fusion.1:pred[8]"), (4.0, 4.25, "jit_dynamic_slice/sort_of_another:s32[4]")]
    w = types.SimpleNamespace(trace=types.SimpleNamespace(devices={0: ops}), busy_s={0: 3.75}, rows=2_000_000)
    assert program_spans.sort_busy_s(w) == 3.0
    read = lambda name: run.load_module("layer_metrics", name).read(w)  # noqa: E731
    assert read("sort_busy_ms_per_mrow") == 1500.0 and read("nonsort_busy_ms_per_mrow") == 375.0
    assert read("sort_busy_ms_per_mrow") + read("nonsort_busy_ms_per_mrow") == read("kernel_busy_ms_per_mrow")
    w.trace.devices = {0: [(0.0, 2.0, "jit_f/sort.12:u32[2,8]")]}  # the parent's names: no program of its own name
    assert read("sort_busy_ms_per_mrow") is None and read("nonsort_busy_ms_per_mrow") is None


def test_the_loader_reads_names_stats_and_threads_from_a_profiler_trace(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation

    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 1
    options.python_tracer_level = 0

    def pool():
        with TraceAnnotation("pt:decode.file", op=4, parent="decode.keys", rows=9):
            pass

    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with TraceAnnotation("pb:window"):
        with TraceAnnotation("pt:read_all", op=4, splits=1):
            with TraceAnnotation("pt:decode.keys", op=4, parent="read_all"):
                worker = threading.Thread(target=pool)
                worker.start()
                worker.join(timeout=10)
    with TraceAnnotation("pt:read_all", op=5):  # after the window
        pass
    jax.profiler.stop_trace()
    path = trace_reader.newest_xplane(str(tmp_path))
    spans = program_spans.load(path)
    assert program_spans.load(path) is spans  # one parse a file
    assert [o.op for o in spans.operations] == [4] and spans.window is not None
    by_name = {sp.name: sp for sp in spans.spans if sp.op == 4}
    assert set(by_name) == {"read_all", "decode.keys", "decode.file"}
    assert by_name["decode.file"].stats == {"op": 4, "parent": "decode.keys", "rows": 9}
    assert by_name["decode.file"].line != by_name["read_all"].line == by_name["decode.keys"].line
    assert by_name["read_all"].children == [by_name["decode.keys"]] and by_name["decode.keys"].children == []
    w = types.SimpleNamespace(trace=types.SimpleNamespace(path=path))
    assert program_spans.median_busy_ms(w, "decode.file") >= 0 and program_spans.median_self_ms(w, "gather") == 0
    program_spans.describe(path)  # the look by hand: no device side in this trace, and it says so


def test_every_new_metric_has_its_entry_and_its_reader():
    assert check_manifest.check() == []
    bench = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert NEW_METRICS <= set(entries)
    assert [m["name"] for m in bench["per_layer"][-len(NEW_METRICS):]] and set(
        m["name"] for m in bench["per_layer"][-len(NEW_METRICS):]) == NEW_METRICS  # appended, nothing between
    for name in NEW_METRICS:
        assert entries[name]["moves"] == "rows_per_s" and "workloads" not in entries[name]
        assert callable(run.load_module("layer_metrics", name).read)
