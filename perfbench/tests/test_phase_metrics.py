"""The readers of the phases inside a data file (PR 38): `decode.format` and
`decode.convert` inside `decode.file`, the four `encode.*` inside
`file.write`, and `compact.join` inside `compact.read`. Each sums its span
per `pb:op` over all threads, median over the operations. On hand-made spans
every answer is known, and adding the phases moves no accepted reader; on a
trace the profiler wrote on the CPU around a real read and a real full
compaction each reads what the trace holds; on a program without the spans
each reads 0.

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests -q -p no:cacheprovider
"""

import json
import os
import statistics
import types

import numpy as np
import pytest

import ingest_spans
import program_spans
import run
from ingest_spans import IngestSpans
from program_spans import ProgramSpans, Span

CLIENT, POOL = (0, 0), [(0, 1), (0, 2)]
PHASES = {"decode_format_ms_p50": "decode.format", "decode_convert_ms_p50": "decode.convert",
          "encode_arrow_ms_p50": "encode.arrow", "encode_format_ms_p50": "encode.format",
          "encode_put_ms_p50": "encode.put", "encode_stats_ms_p50": "encode.stats",
          "compact_join_ms_p50": "compact.join"}
ALL_CELLS = ["tableformat-10m.merge-read", "tableread-1m.merge-read", "pk-8bucket-mesh.merge-read",
             "cdc-upsert.zipf-ingest", "dedicated-compact-10m.full-compaction"]
WRITING_CELLS = ["cdc-upsert.zipf-ingest", "dedicated-compact-10m.full-compaction"]
# the accepted readers the phases lie under: each must read the same with and without them
ACCEPTED = ("file_write_ms_p50", "fc_file_write_ms_p50", "compact_ms_p50", "flush_ms_p50", "fc_read_ms_p50",
            "ingest_unattributed_share", "fc_unattributed_share", "files_write_wall_ms_p50")


def s(name, start, end, line=CLIENT, op=1, **stats):
    return Span(name, start, end, line, {"op": op, **stats})


def a_round(t0, phases=True):
    """One compaction round of 10 s on the client's thread: a read head of 4 s
    (two files decoded on the pool, 1.5 s each, then a join of 1 s), and two
    files of 2 s written on the pool under a `files.write` of 2.5 s. Each file
    decode is 1 s of the library and 0.25 s of conversion; each file write
    0.25 s of arrow, 1.25 s of encode, 0.125 s of put and 0.25 s of stats."""
    out = [s("compact", t0, t0 + 10.0), s("compact.read", t0 + 1.0, t0 + 5.0),
           s("files.write", t0 + 6.0, t0 + 8.5)]
    for i, line in enumerate(POOL):
        d, f = t0 + 1.25 + i * 0.5, t0 + 6.25
        out += [s("decode.file", d, d + 1.5, line), s("file.write", f, f + 2.0, line)]
        if phases:
            out += [s("decode.format", d, d + 0.5, line), s("decode.format", d + 0.5, d + 1.0, line),
                    s("decode.convert", d + 1.0, d + 1.25, line),
                    s("encode.arrow", f, f + 0.25, line), s("encode.format", f + 0.25, f + 1.5, line),
                    s("encode.put", f + 1.5, f + 1.625, line), s("encode.stats", f + 1.625, f + 1.875, line)]
    if phases:
        out.append(s("compact.join", t0 + 3.75, t0 + 4.75))
    return out


def _reader(name):
    return run.load_module("layer_metrics", name).read


def _read(name, spans, monkeypatch):
    monkeypatch.setattr(ingest_spans, "load", lambda path: spans)
    monkeypatch.setattr(run.load_module("layer_metrics", "fc_unattributed_share"), "load", lambda path: spans)
    return _reader(name)(types.SimpleNamespace(trace=types.SimpleNamespace(path="hand-made", spans={})))


def test_each_phase_reads_its_spans_busy_time_per_operation(monkeypatch):
    ops = [(100.0, 111.0, CLIENT), (200.0, 211.0, CLIENT), (300.0, 311.0, CLIENT)]
    spans = IngestSpans(a_round(100.0) + a_round(200.0) + [s("compact", 300.0, 301.0)], ops, None)
    want = {"decode_format_ms_p50": 2000.0, "decode_convert_ms_p50": 500.0, "encode_arrow_ms_p50": 500.0,
            "encode_format_ms_p50": 2500.0, "encode_put_ms_p50": 250.0, "encode_stats_ms_p50": 500.0,
            "compact_join_ms_p50": 1000.0}
    assert {name: _read(name, spans, monkeypatch) for name in PHASES} == want
    # the phases cover their containers: the two decodes' 3 s less 0.5, the two files' 4 s less 0.25
    assert want["decode_format_ms_p50"] + want["decode_convert_ms_p50"] == 2500.0
    assert sum(want[n] for n in PHASES if n.startswith("encode")) == 3750.0


def test_the_phases_move_no_accepted_reader(monkeypatch):
    ops = [(100.0, 111.0, CLIENT), (200.0, 211.0, CLIENT)]
    without = {name: _read(name, IngestSpans(a_round(100.0, False) + a_round(200.0, False), ops, None), monkeypatch)
               for name in ACCEPTED + tuple(PHASES)}
    with_phases = {name: _read(name, IngestSpans(a_round(100.0) + a_round(200.0), ops, None), monkeypatch)
                   for name in ACCEPTED}
    assert with_phases == {name: without[name] for name in ACCEPTED}
    assert without["fc_read_ms_p50"] == 4000.0 and without["file_write_ms_p50"] == 4000.0
    assert {name: without[name] for name in PHASES} == dict.fromkeys(PHASES, 0.0)  # a program without the spans


def test_a_decode_on_the_reading_thread_keeps_the_wait_and_the_busy_time():
    """`decode_wait_ms_p50` takes the `decode` family's self time on the
    reading thread and `decode_busy_ms_p50` the `decode.file` spans: a
    `decode.file` there (`scan.parallelism` 1) with its phases inside reads
    the same in both."""
    def read_all(with_phases):
        out = [s("read_all", 0.0, 4.0), s("split", 0.0, 4.0), s("decode.keys", 0.5, 3.0),
               s("decode.file", 0.75, 2.75)]
        if with_phases:
            out += [s("decode.format", 0.75, 1.25), s("decode.format", 1.25, 2.25), s("decode.convert", 2.25, 2.5)]
        return ProgramSpans(out)

    for reduce in (lambda p: p.self_ms(("decode",)), lambda p: p.busy_ms(("decode.file",)),
                   lambda p: [p.unattributed_share()]):
        assert reduce(read_all(True)) == reduce(read_all(False))
    assert read_all(True).self_ms(("decode",)) == [2500.0] and read_all(True).busy_ms(("decode.file",)) == [2000.0]
    assert not any(program_spans.matches(name, ("decode.file",)) for name in ("decode.format", "decode.convert"))


def test_the_seven_entries_list_their_cells():
    by_name = {m["name"]: m for m in json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))["per_layer"]}
    for name in PHASES:
        m = by_name[name]
        assert m["workloads"] == (ALL_CELLS if name.startswith("decode") else WRITING_CELLS)
        assert (m["unit"], m["better"], m["source"], m["moves"]) == ("ms", "lower", "program_span", "rows_per_s")
        assert m["layer"] == ("file decode" if name.startswith("decode") else "write path")


# ---- on a trace the profiler wrote ----------------------------------------------------

def _direct_median_ms(path, name):
    """The metric straight from the trace: per `pb:op` of the window, the
    `pt:<name>` events of any line that begin inside it, summed; the median."""
    from jax.profiler import ProfileData

    events, ops = [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == "pt:" + name:
                    events.append((e.start_ns, e.duration_ns))
                elif e.name == "pb:op":
                    ops.append((e.start_ns, e.start_ns + e.duration_ns))
    return statistics.median(sum(d for t, d in events if a <= t < b) / 1e6 for a, b in ops)


@pytest.fixture(scope="module")
def traced_ops(tmp_path_factory):
    """Four `pb:op` intervals in a `pb:window`, as run.py opens them: a cold
    read of an ORC table, a full compaction of it, a commit and a second full
    compaction, and one that does nothing. Then a trace of the same shape from a program that opens none of the
    phases (the parent's spans alone), and one without `pb:op`."""
    import jax

    import paimon_tpu as pt
    from paimon_tpu.catalog import FileSystemCatalog
    from paimon_tpu.metrics import span
    from paimon_tpu.table.compactor import DedicatedCompactor
    from paimon_tpu.utils.cache import clear_all

    import trace_reader

    tmp = tmp_path_factory.mktemp("phases")
    table = FileSystemCatalog(str(tmp / "warehouse"), commit_user="phases").create_table(
        "db.t", pt.RowType.of(("id", pt.BIGINT(False)), ("v", pt.DOUBLE()), ("s", pt.STRING())),
        primary_keys=["id"], options={"bucket": "1", "file.format": "orc", "write-only": "true"})
    rng = np.random.default_rng(5)

    def commit(r):
        ids = np.sort(rng.choice(20_000, 5_000, replace=False)).astype(np.int64)
        wb = table.new_batch_write_builder()
        w = wb.new_write()
        w.write({"id": ids, "v": ids * 0.5 + r, "s": np.array([f"s{i % 11}" for i in ids], dtype=object)})
        wb.new_commit().commit(w.prepare_commit())

    for r in range(3):
        commit(r)

    def read():
        rb = table.new_read_builder()
        rb.new_read().read_all(rb.new_scan().plan())

    def trace(directory, ops, with_ops=True):
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = 1
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(directory), profiler_options=options)
        with jax.profiler.TraceAnnotation("pb:window"):
            for op in ops:
                with jax.profiler.TraceAnnotation("pb:op" if with_ops else "other"):
                    op()
        jax.profiler.stop_trace()
        return types.SimpleNamespace(trace=types.SimpleNamespace(path=trace_reader.newest_xplane(str(directory))))

    def parent_spans():  # what the parent opens in a read and a rewrite: the containers, never the phases
        for name in ("decode.file", "compact.read", "file.write"):
            with span(name):
                pass

    clear_all()
    def compact():
        assert DedicatedCompactor(table).run_once(full=True) is True

    program = trace(tmp / "program", [read, compact, lambda: (commit(3), compact()), lambda: None])
    parent = trace(tmp / "parent", [parent_spans, parent_spans, parent_spans, lambda: None])
    bare = trace(tmp / "bare", [read], with_ops=False)
    return {"program": program, "parent": parent, "bare": bare}


@pytest.mark.parametrize("name", sorted(PHASES))
def test_each_reader_on_a_cpu_trace_of_a_read_and_a_full_compaction(traced_ops, name):
    w = traced_ops["program"]
    value = _reader(name)(w)
    assert value == pytest.approx(_direct_median_ms(w.trace.path, PHASES[name]))
    per_op = ingest_spans.load(w.trace.path).per_op_ms(PHASES[name])
    assert len(per_op) == 4 and per_op[3] == 0  # the fourth operation does nothing
    # the read decodes (no encode, no join); a compaction decodes, joins and encodes, and so does a commit's file
    assert (per_op[0] > 0) == name.startswith("decode") and per_op[1] > 0 and per_op[2] > 0 and value > 0
    assert _reader(name)(traced_ops["parent"]) == 0  # operations, and no such span in them
    assert _reader(name)(traced_ops["bare"]) is None  # no pb:op in the trace: nothing to read
