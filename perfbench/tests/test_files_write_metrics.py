"""The readers of the files written side by side (PR 37): the wall of a
write's fan-out, `files_write_wall_ms_p50`, beside the files' busy time over
all threads, `file_write_ms_p50`, and the share of files the pool wrote,
`file_write_pool_share`; and what they read of a program that opens no such
span and counts no such file.

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests -q -p no:cacheprovider
"""

import json
import os
import types

import run
from ingest_spans import IngestSpans
from program_spans import Span

CLIENT, POOL = (0, 0), [(0, 1), (0, 2), (0, 3)]
CELLS = ["cdc-upsert.zipf-ingest", "dedicated-compact-10m.full-compaction"]


def s(name, start, end, line=CLIENT):
    return Span(name, start, end, line, {"op": 1})


def a_round(t0, files):
    """A rewrite of `files` files of 1 s each: one is written on the caller's
    thread under no `files.write`, several lie on the pool's threads inside a
    `files.write` of 1.25 s (the fan-out, the slowest file, the wake-up)."""
    if files == 1:
        return [s("compact", t0, t0 + 3.0), s("file.write", t0 + 1.0, t0 + 2.0)]
    return [s("compact", t0, t0 + 3.0), s("files.write", t0 + 1.0, t0 + 2.25)] + \
        [s("file.write", t0 + 1.125, t0 + 2.125, POOL[i]) for i in range(files)]


def _read(name, spans, monkeypatch):
    import ingest_spans

    monkeypatch.setattr(ingest_spans, "load", lambda path: spans)
    return run.load_module("layer_metrics", name).read(types.SimpleNamespace(trace=types.SimpleNamespace(path="hand-made")))


def test_the_wall_of_the_fan_out_beside_the_files_busy_time(monkeypatch):
    ops = [(100.0, 104.0, CLIENT), (200.0, 204.0, CLIENT), (300.0, 304.0, CLIENT)]
    spans = IngestSpans(a_round(100.0, 3) + a_round(200.0, 3) + a_round(300.0, 1), ops, None)
    assert _read("files_write_wall_ms_p50", spans, monkeypatch) == 1250.0
    assert _read("file_write_ms_p50", spans, monkeypatch) == 3000.0  # three files' seconds, wherever they ran
    assert _read("fc_file_write_ms_p50", spans, monkeypatch) == 3000.0
    # compact's reader takes out the file.write on compact's own thread only: a known blind spot (PERF.md section 7)
    assert _read("compact_ms_p50", spans, monkeypatch) == 3000.0


def test_most_operations_writing_one_file_read_a_wall_of_zero(monkeypatch):
    ops = [(100.0, 104.0, CLIENT), (200.0, 204.0, CLIENT), (300.0, 304.0, CLIENT)]
    spans = IngestSpans(a_round(100.0, 1) + a_round(200.0, 1) + a_round(300.0, 2), ops, None)
    assert _read("files_write_wall_ms_p50", spans, monkeypatch) == 0.0
    parent = IngestSpans([s("compact", 100.0, 103.0), s("file.write", 101.0, 102.0), s("file.write", 102.0, 103.0)],
                         ops[:1], None)
    assert _read("files_write_wall_ms_p50", parent, monkeypatch) == 0.0  # a program without the span
    assert _read("files_write_wall_ms_p50", IngestSpans([], [], None), monkeypatch) is None  # no pb:op at all


def test_the_share_of_files_the_pool_wrote_and_a_program_that_counts_none():
    read = run.load_module("layer_metrics", "file_write_pool_share").read
    before = {"datafile": {"files_written": 20, "files_written_on_pool": 20, "files_decoded": 13}}
    after = {"datafile": {"files_written": 60, "files_written_on_pool": 50, "files_decoded": 65}}
    assert read(types.SimpleNamespace(counters_before=before, counters_after=after)) == 0.75
    one_a_call = {"datafile": {"files_written": 70, "files_decoded": 65}}  # a counter never touched is not there
    started = {"datafile": {"files_written": 60, "files_decoded": 65}}
    assert read(types.SimpleNamespace(counters_before=started, counters_after=one_a_call)) == 0.0
    assert read(types.SimpleNamespace(counters_before=one_a_call, counters_after=one_a_call)) == 0.0  # a window of reads
    parent = {"datafile": {"files_decoded": 65}}  # the group is there, the counters are not
    assert read(types.SimpleNamespace(counters_before={}, counters_after=parent)) is None
    assert read(types.SimpleNamespace(counters_before={}, counters_after={"read": {"ops": 3}})) is None


def test_both_metrics_are_listed_for_the_two_writing_cells():
    by_name = {m["name"]: m for m in json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))["per_layer"]}
    wall, share = by_name["files_write_wall_ms_p50"], by_name["file_write_pool_share"]
    for m in (wall, share):
        assert m["workloads"] == CELLS and m["layer"] == "write path" and m["moves"] == "rows_per_s"
    assert (wall["better"], wall["source"], share["better"], share["source"]) == \
        ("lower", "program_span", "higher", "program_counter")
