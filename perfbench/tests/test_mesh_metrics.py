"""The readers of the mesh layer (`perfbench/layer_metrics/mesh_*.py`) on
hand-made operations where every answer is known: an operation of the mesh
engine with two `shard_map` calls, an operation of the single-device path,
which reads 0 (the skew 1.0), and a program without operations or counters,
which reads nothing."""

import types

import pytest

import program_spans
import run
from program_spans import ProgramSpans, Span

READER, FEEDER = (0, 0), (0, 1)
MESH_METRICS = ("mesh_batches_per_op", "mesh_feed_wait_ms_p50", "mesh_pack_ms_p50", "mesh_batch_ms_p50",
                "mesh_busy_skew")


def s(name, start, end, op, line=READER, **stats):
    return Span(name, start, end, line, {"op": op, **stats})


def mesh_operation(op, t0):
    """read_all of 10 s with two rounds. A round: the reader waits 1 s for the
    feeder (of it 0.75 s in the pipeline's own wait span), then in the first
    split's continuation plans 0.5 s, stacks 0.25 s and runs a batch of 1.5 s
    (upload 0.25 s, call 0.125 s, download 1 s, 0.125 s its own), then gathers
    0.5 s. The feeder's threads decode meanwhile."""
    spans = [s("read_all", t0, t0 + 10, op)]
    for r, at in enumerate((t0 + 0.5, t0 + 5.0)):
        spans += [
            s("mesh.feed", at, at + 1.0, op, parent="read_all", shards=4),
            s("pipeline.scan.wait", at + 0.125, at + 0.875, op, parent="mesh.feed"),
            s("split", at + 1.0, at + 4.0, op, parent="read_all"),
            s("mesh.plan", at + 1.0, at + 1.5, op, parent="split", jobs=4),
            s("mesh.stack", at + 1.5, at + 1.75, op, parent="split", shards=4),
            s("mesh.batch", at + 1.75, at + 3.25, op, parent="split", shards=4),
            s("mesh.h2d", at + 1.75, at + 2.0, op, parent="mesh.batch"),
            s("mesh.run", at + 2.0, at + 2.125, op, parent="mesh.batch"),
            s("mesh.d2h", at + 2.125, at + 3.125, op, parent="mesh.batch"),
            s("gather", at + 3.25, at + 3.75, op, parent="split"),
            s("decode.all", at, at + 0.75, op, FEEDER, parent="read_all"),
            s("mesh.plan", at, at + 9.0, op, FEEDER),  # not the reader's: on another thread it is no one's wait
        ]
    return spans


def single_operation(op, t0):
    return [s("read_all", t0, t0 + 4, op), s("split", t0, t0 + 3.5, op, parent="read_all"),
            s("merge.dispatch", t0 + 0.5, t0 + 1.5, op, parent="split"), s("gather", t0 + 2, t0 + 3, op, parent="split")]


def _window(monkeypatch, spans, before=None, after=None, busy=None):
    monkeypatch.setattr(program_spans, "load", lambda path: ProgramSpans(spans))
    return types.SimpleNamespace(trace=types.SimpleNamespace(path="hand-made"), counters_before=before or {},
                                 counters_after=after or {}, busy_s=busy or {})


def _read(name, w):
    return run.load_module("layer_metrics", name).read(w)


def test_a_mesh_operation_with_two_batches(monkeypatch):
    w = _window(
        monkeypatch, mesh_operation(7, 100.0) + mesh_operation(8, 200.0) + mesh_operation(9, 300.0),
        before={"read": {"ops": 4}, "mesh": {"shards": 9, "pad_rows": 100}},
        after={"read": {"ops": 7}, "mesh": {"shards": 15, "pad_rows": 400}},
        busy={0: 3.0, 1: 2.0, 2: 2.0, 3: 1.0})
    assert _read("mesh_batches_per_op", w) == 2.0
    assert _read("mesh_feed_wait_ms_p50", w) == 2000.0  # both rounds' waits, the pipeline's own span included once
    assert _read("mesh_pack_ms_p50", w) == 1500.0  # (0.5 + 0.25) s a round, the feeder thread's span left out
    assert _read("mesh_batch_ms_p50", w) == 3000.0  # 1.5 s a call: the three inside and the call's own 0.125 s
    assert _read("mesh_busy_skew", w) == 1.5  # 3 s on the busiest of four that average 2 s
    spans = ProgramSpans(mesh_operation(7, 100.0))
    assert spans.self_ms(("mesh.batch",)) == [250.0] and spans.self_ms(("mesh.d2h",)) == [2000.0]
    # read_all's own 2 s, and 0.25 s a split between the batch's results and the end of the continuation
    assert spans.unattributed_share() == pytest.approx((2.0 + 2 * 0.25) / 10)


def test_a_single_device_operation_reads_zero_and_an_even_skew(monkeypatch):
    w = _window(monkeypatch, single_operation(3, 10.0) + single_operation(4, 20.0),
                before={"read": {"ops": 1}}, after={"read": {"ops": 3}}, busy={0: 0.75})
    for name in MESH_METRICS[:4]:
        assert _read(name, w) == 0, name
    assert _read("mesh_busy_skew", w) == 1.0


def test_a_program_without_operations_or_counters_reads_nothing(monkeypatch):
    w = _window(monkeypatch, [s("plan", 0, 1, 0)], before={"scan": {"plans": 1}}, after={"scan": {"plans": 2}})
    for name in MESH_METRICS:
        assert _read(name, w) is None, name
    idle = _window(monkeypatch, [], busy={0: 0.0, 1: 0.0})
    assert _read("mesh_busy_skew", idle) is None  # no device ran anything: nothing to divide by


@pytest.mark.parametrize("name", MESH_METRICS)
def test_each_reader_is_a_file_of_its_metrics_name_with_a_docstring(name):
    module = run.load_module("layer_metrics", name)
    assert callable(module.read) and module.__doc__.startswith("Mesh: ")
