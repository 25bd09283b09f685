"""The `ingest` op and its yardstick, proved to fail where they must: both
controls of reference_ingest.py come out not `correct`, a commit that is not
acknowledged shows in `operations_with_wrong_row_count`, and the write path's
readers on hand-made spans where every answer is known. On the CPU at a size a
test run can hold; the device kernels are pinned as the repo's own tests pin
them.

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests -q -p no:cacheprovider
"""

import json
import os
import types

import numpy as np
import pytest

import ingest_spans
import reference
import reference_ingest
import run
from ingest_spans import IngestSpans
from program_spans import Span

BENCH = run.HERE
KEYS, BATCH = 60_000, 4_000
CONFIG = {**json.load(open(os.path.join(BENCH, "configs", "cdc-upsert.json"))), "rows": KEYS,
          "commit_interval_rows": BATCH}
TRAFFIC = {"op": "ingest", "batch_rows": BATCH, "pool_batches": 16}
RUN_COLUMNS = 4  # c1, c4, d1, d3: the columns of the schema that depend on the writing run


def _failed(numbers):
    return [name for name, value, limit in numbers if value > limit]


def _value(numbers, name):
    return {n: v for n, v, _ in numbers}[name]


@pytest.fixture()
def op(tmp_path):
    made = run.load_module("ops", "ingest").Op(CONFIG, 2**31 + 5, str(tmp_path), run.Spans(), traffic=TRAFFIC)
    yield made
    made.writer.close()


def test_fourteen_commits_are_correct_and_both_controls_are_not(op):
    acks = [op() for _ in range(14)]
    rows_out = [op.rows_of(a) for a in acks]
    assert rows_out == [KEYS] * 14 and sum(len(a[0]) == 2 for a in acks) >= 2  # some commits compacted
    numbers = run._compare(op, acks[-1], rows_out)
    assert [n for n, _, _ in numbers] == ["operations_with_wrong_row_count", "rows_out_minus_reference", "keys_missing",
                                          "keys_invented", "keys_duplicated", "columns_missing", "null_cells",
                                          "wrong_cells"]
    assert _failed(numbers) == [] and all(limit == 0 for _, _, limit in numbers)
    got = op.output_columns(None)
    batches = [(i, op.positions[i]) for i in range(1, 15)]
    lost = reference.compare(got, {n: (v, None) for n, v in reference_ingest.control_last_commit_lost(
        op.ids, batches, CONFIG["schema"]).items()})
    # every key whose last writer is run 14 differs, in the four columns that follow the writing run
    assert _failed(lost) == ["wrong_cells"]
    assert _value(lost, "wrong_cells") == RUN_COLUMNS * len(np.unique(op.positions[14]))
    first = reference.compare(got, {n: (v, None) for n, v in reference_ingest.control_first_writer(
        op.ids, batches, CONFIG["schema"]).items()})
    touched = len(np.unique(np.concatenate([p for _, p in batches])))
    assert _failed(first) == ["wrong_cells"] and _value(first, "wrong_cells") == RUN_COLUMNS * touched


def test_a_commit_that_is_skipped_is_counted_and_read_back_as_wrong(op):
    acks = [op() for _ in range(3)]
    real = op.committer.commit_messages
    op.committer.commit_messages = lambda identifier, messages: []  # the fourth commit never lands
    acks.append(op())
    op.committer.commit_messages = real
    rows_out = [op.rows_of(a) for a in acks]
    assert rows_out == [KEYS, KEYS, KEYS, 0]
    numbers = run._compare(op, acks[-1], rows_out)
    assert _value(numbers, "operations_with_wrong_row_count") == 1
    assert "wrong_cells" in _failed(numbers)  # and the table lacks what the reference holds acknowledged


def test_an_acknowledgement_out_of_order_counts_for_nothing(op):
    ack = op()
    assert op.rows_of(ack) == KEYS
    assert op.rows_of(((ack[0][0],), ack[0][0])) == 0  # a snapshot id the op had already seen
    assert op.rows_of(((), 0)) == 0


def test_the_window_may_not_outrun_the_pool(op):
    for _ in range(TRAFFIC["pool_batches"]):
        op()
    with pytest.raises(RuntimeError, match="outran the pool"):
        op()


def test_the_op_finds_its_traffic_from_the_manifest():
    ingest = run.load_module("ops", "ingest")
    assert ingest._traffic_of("cdc-upsert") == json.load(open(os.path.join(BENCH, "traffic", "zipf-ingest.json")))
    with pytest.raises(ValueError, match="0 ingest cells"):
        ingest._traffic_of("tableread-1m")


# ---- the write path's readers on hand-made spans --------------------------------

CLIENT, WORKER, POOL = (0, 0), (0, 1), (0, 2)


def s(name, start, end, line=CLIENT, **stats):
    return Span(name, start, end, line, {"op": 1, **stats})


def one_commit(t0):
    """An operation of 10 s: write 1 s; prepare_commit 7 s holding the flush's
    dispatch half (2 s, of it merge.dispatch 1 s) and the wait for the worker
    (4.5 s); on the worker the flush's landing half (2 s, of it a file of
    1.5 s) and a round of compaction (2.5 s: the pick 0.25 s, a file decoded
    on the pool 0.5 s, a file written 1 s); commit 1.5 s."""
    return [
        s("write", t0, t0 + 1),
        s("prepare_commit", t0 + 1, t0 + 8),
        s("flush", t0 + 1.25, t0 + 3.25, rows_in=100),
        s("merge.dispatch", t0 + 1.5, t0 + 2.5),
        s("flush.wait", t0 + 3.25, t0 + 7.75),
        s("flush", t0 + 3.25, t0 + 5.25, WORKER, rows_out=60),
        s("file.write", t0 + 3.5, t0 + 5.0, WORKER),
        s("compact", t0 + 5.25, t0 + 7.75, WORKER),
        s("compact.pick", t0 + 5.25, t0 + 5.5, WORKER),
        s("decode.file", t0 + 5.5, t0 + 6.0, POOL),
        s("file.write", t0 + 6.5, t0 + 7.5, WORKER),
        s("commit", t0 + 8.25, t0 + 9.75),
    ]


def test_spans_are_summed_per_operation_over_all_threads():
    spans = IngestSpans(one_commit(100.0) + one_commit(200.0), [(100.0, 110.0, CLIENT), (200.0, 210.0, CLIENT)], None)
    assert spans.per_op_ms("write") == [1000.0, 1000.0]
    assert spans.per_op_ms("flush") == [4000.0, 4000.0]  # both halves, not flush.wait
    assert spans.per_op_ms("flush", less=("file.write",)) == [2500.0, 2500.0]
    assert spans.per_op_ms("compact", less=("file.write",)) == [1500.0, 1500.0]
    assert spans.per_op_ms("file.write") == [2500.0, 2500.0]
    assert spans.per_op_ms("commit") == [1500.0, 1500.0]
    assert spans.per_op_ms("no.such") == [0.0, 0.0]
    # the client's 10 s: under write, the flush's leaves (merge.dispatch), flush.wait and commit lie 1 + 1 + 4.5 + 1.5
    assert spans.unattributed_share() == pytest.approx(1 - 8.0 / 10.0)


def test_an_operation_that_opened_none_reads_zero_and_operations_outside_the_window_are_left_out():
    ops = [(100.0, 110.0, CLIENT), (200.0, 210.0, CLIENT), (300.0, 310.0, CLIENT)]
    spans = IngestSpans(one_commit(100.0) + one_commit(300.0), ops, (150.0, 305.0))
    assert spans.per_op_ms("write") == [0.0, 1000.0]  # the second opened none; the third began inside, so it counts
    assert spans.unattributed_share() == pytest.approx((10.0 + 2.0) / 20.0)
    parent = IngestSpans([s("merge.dispatch", 101.0, 102.0)], ops[:1], None)  # a program without the write path's spans
    assert parent.per_op_ms("flush", less=("file.write",)) == [0.0] and parent.unattributed_share() == pytest.approx(0.9)
    assert IngestSpans(one_commit(100.0), [], None).per_op_ms("write") == []
    assert IngestSpans(one_commit(100.0), [], None).unattributed_share() is None


def test_readers_on_a_trace_the_profiler_wrote(tmp_path):
    """Two `pb:op` intervals around the program's own span objects, written by
    the profiler and read back by the readers; and a trace with no `pb:op`."""
    import time

    import jax

    from paimon_tpu.metrics import span

    def trace(directory, with_ops):
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = 1
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(directory), profiler_options=options)
        with jax.profiler.TraceAnnotation("pb:window"):
            for i in range(2):
                with jax.profiler.TraceAnnotation("pb:op") if with_ops else jax.profiler.TraceAnnotation("other"):
                    if i == 0:
                        with span("write", new_op=True):
                            time.sleep(0.02)
                        with span("compact"):
                            with span("file.write"):
                                time.sleep(0.03)
                            time.sleep(0.01)
                    else:
                        time.sleep(0.01)
        jax.profiler.stop_trace()
        import trace_reader

        return types.SimpleNamespace(trace=types.SimpleNamespace(path=trace_reader.newest_xplane(str(directory))))

    read = lambda name: run.load_module("layer_metrics", name).read  # noqa: E731
    w = trace(tmp_path / "ops", True)
    per_op = ingest_spans.load(w.trace.path)
    assert len(per_op.ops) == 2
    writes, files, rounds = per_op.per_op_ms("write"), per_op.per_op_ms("file.write"), per_op.per_op_ms(
        "compact", less=("file.write",))
    assert writes[0] >= 20 and writes[1] == 0 and files[0] >= 30 and files[1] == 0 and 10 <= rounds[0] < 30
    assert read("write_buffer_ms_p50")(w) == pytest.approx(writes[0] / 2)  # the median of two
    assert read("file_write_ms_p50")(w) == pytest.approx(files[0] / 2)
    assert read("compact_ms_p50")(w) == pytest.approx(rounds[0] / 2)
    assert read("flush_ms_p50")(w) == 0 and read("commit_ms_p50")(w) == 0  # operations, but no such span: 0
    assert 0 < read("ingest_unattributed_share")(w) < 0.5  # the compact container's own 10 ms, the second op's 10 ms
    bare = trace(tmp_path / "bare", False)
    for name in ("write_buffer_ms_p50", "flush_ms_p50", "compact_ms_p50", "file_write_ms_p50", "commit_ms_p50",
                 "ingest_unattributed_share"):
        assert read(name)(bare) is None  # no pb:op in the trace: nothing to read


def test_write_amp_reads_the_two_counters_over_the_windows_rows():
    read = run.load_module("layer_metrics", "write_amp").read
    before = {"flush": {"rows_out": 100}, "compaction": {"rows_out": 1_000}}
    after = {"flush": {"rows_out": 560}, "compaction": {"rows_out": 3_540}}
    assert read(types.SimpleNamespace(counters_before=before, counters_after=after, rows=1_000)) == 3.0
    assert read(types.SimpleNamespace(counters_before={}, counters_after={"flush": {"rows_out": 46}}, rows=100)) == 0.46
    parent = types.SimpleNamespace(counters_before={}, counters_after={"compaction": {"compactions": 3}}, rows=100)
    assert read(parent) is None  # a program without flush{...}: the line leaves the metric out
