"""The `aggregate-read` op and its yardstick, proved to fail where they must:
the cell is `correct` at 60,000 rows with its layout and functions; each of
the six controls of `reference_aggregate` is not; the `agg_*` readers read
hand-made spans, counters and device operations, read 0 or nothing on a
program without the aggregation's spans and counters, and nothing on a trace
without operations. On the CPU, the device kernels pinned as the repo's own
tests pin them.

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests -q -p no:cacheprovider
"""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import reference
import reference_aggregate as ref
import run
from program_spans import ProgramSpans, Span

BENCH, ROOT = run.HERE, run.ROOT
CELL = "aggregation-6m.rollup-read"
ROWS, KEYS = 60_000, 24_000  # the cell's 2.5 input rows a key
FULL = json.load(open(os.path.join(BENCH, "configs", "aggregation-6m.json")))
CONFIG = {**FULL, "name": "tinyagg", "rows": ROWS, "keys": KEYS, "table": "bench.tinyagg"}
SEED = 2**31 + 51
AGG_METRICS = ("agg_read_ms_p50", "agg_merge_ms_p50", "agg_reduce_ms_p50", "agg_host_field_share",
               "agg_unattributed_share")


def _failed(numbers):
    return [name for name, value, limit in numbers if value > limit]


def _tiny_root(tmp_path, config):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    os.makedirs(tmp_path / "configs")
    json.dump(config, open(tmp_path / "configs" / "tinyagg.json", "w"))
    entry = next(c for c in bench["configs"] if c["name"] == "aggregation-6m")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    bench["configs"] = [dict(entry, name="tinyagg", file="configs/tinyagg.json")]
    bench["workloads"] = [dict(cell, name="tinyagg.rollup-read", config="tinyagg")]
    # the metrics that list the cell and the listless ones; the peaks table has no CPU
    bench["per_layer"] = [dict(m, workloads=["tinyagg.rollup-read"]) for m in bench["per_layer"]
                          if CELL in m.get("workloads", [CELL]) and "roofline" not in m["name"]]
    json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))
    return str(tmp_path)


@pytest.fixture(scope="module")
def op(tmp_path_factory):
    return run.load_module("ops", "aggregate_read").Op(CONFIG, SEED, str(tmp_path_factory.mktemp("op")), run.Spans())


def test_the_cell_is_correct_and_its_traced_line_carries_every_metric(tmp_path):
    root = _tiny_root(tmp_path, CONFIG)
    result = run.run_cell("tinyagg.rollup-read", 2**31 + 53, 0.5, False, need_chip=False, root=root)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"rows_per_s", "setup_s"}
    assert len(result["compared"]) == 8 and all(v == {"value": 0, "limit": 0} for v in result["compared"].values())
    traced = run.run_cell("tinyagg.rollup-read", 19, 0.5, True, need_chip=False, root=root)
    assert traced["correct"] is True
    names = {m["name"] for m in json.load(open(os.path.join(root, "BENCHMARK.json")))["per_layer"]}
    assert set(AGG_METRICS) <= names
    assert set(traced["metrics"]) == names - {"device_peak_bytes"}  # the CPU backend keeps no memory_stats
    value = {n: m["value"] for n, m in traced["metrics"].items()}
    for name in AGG_METRICS[:3]:
        assert 0 < value[name] < value["op_ms_p50"], name
    assert sum(value[n] for n in AGG_METRICS[:3]) < value["op_ms_p50"]
    assert value["agg_host_field_share"] == 0.0  # the CPU takes the fused kernel: every field on the device
    assert 0 <= value["agg_unattributed_share"] < 0.5


def test_the_op_reads_the_rollup_of_the_four_sources(op):
    out = op()
    assert op.rows_per_op == ROWS and op.describe()["files_planned"] == 4  # one file a source at this size
    numbers = run._compare(op, out, [op.rows_of(out)])
    assert _failed(numbers) == [] and all(limit == 0 for _, _, limit in numbers)
    want = op.reference_columns()
    assert len(want["id"][0]) == KEYS
    c1, valid = want["c1"][0], want["c1.valid"][0].astype(bool)
    assert 0.6 < (c1[valid] > 2**32).mean() < 0.95 and (want["c4"][0] < 0).any()
    assert 0.97 < valid.mean() < 1.0  # a key is null only where every input was


CONTROLS = {  # control -> the only numbers it breaks
    "control_deduplicate": {"wrong_cells"},
    "control_sum_int32": {"wrong_cells"},
    "control_max_float32": {"wrong_cells"},
    "control_last_value": {"wrong_cells"},
    "control_nulls_as_zeros": {"wrong_cells"},
    "control_stale_snapshot": {"wrong_cells"},
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_each_control_is_not_correct(op, control):
    got = op.output_columns(op())
    broken = getattr(ref, control)(op.seed, op.ids, op.members, CONFIG)
    numbers = reference.compare(got, broken)
    assert set(_failed(numbers)) == CONTROLS[control]
    assert sorted(CONTROLS) == sorted(ref.CONTROLS)


def test_the_controls_break_what_they_say():
    members = ref.make_sources(7, KEYS, CONFIG["sources"])
    ids = ref.key_ids(KEYS)
    good = ref.expected(7, ids, members, CONFIG)

    def differs(other, name):
        return int(np.count_nonzero(np.asarray(other[name][0]) != np.asarray(good[name][0])))

    wrapped = ref.control_sum_int32(7, ids, members, CONFIG)
    assert differs(wrapped, "c1") > KEYS // 2 and differs(wrapped, "d1") == differs(wrapped, "s1") == 0
    rounded = ref.control_max_float32(7, ids, members, CONFIG)
    assert differs(rounded, "d1") > KEYS // 2 and differs(rounded, "c1") == 0
    last = ref.control_last_value(7, ids, members, CONFIG)
    # the last writer's string is null in a tenth of the keys
    assert 0.05 * KEYS < differs(last, "s1.valid") < 0.15 * KEYS and differs(last, "c1") == 0
    stale = ref.control_stale_snapshot(7, ids, members, CONFIG)
    assert np.array_equal(stale["id"][0], good["id"][0])  # source 0 wrote every key
    assert 0.15 * KEYS < differs(stale, "c1") < 0.3 * KEYS  # the keys of source 3
    dedup = ref.control_deduplicate(7, ids, members, CONFIG)
    assert differs(dedup, "c1") > KEYS // 2
    # a DOUBLE's maximum lies in another commit than the key's last in about half the keys
    assert 0.3 * KEYS < differs(dedup, "d1") < 0.8 * KEYS


def test_check_manifest_passes():
    done = subprocess.run([sys.executable, os.path.join(BENCH, "check_manifest.py")], capture_output=True, text=True)
    assert done.returncode == 0, done.stdout


# ---- the readers on hand-made spans -------------------------------------------

READER, POOL = (0, 0), (0, 1)


def _s(name, start, end, op, line=READER):
    return Span(name, start, end, line, {"op": op})


def one_read(t0, op, route="planned"):
    """An operation of 10 s: plan 0.5 s; read_all 9.5 s holding a split:
    decode.all 2 s (the pool's decodes beside it), read.join 1 s,
    lanes.encode 0.5 s, then on the planned route merge.dispatch 1 s holding
    lanes.compress 0.25 s, merge.resolve 0.5 s and agg.reduce 3.5 s holding
    three agg.field; on the fused route agg.reduce 5 s holding the lane
    packing (lanes.compress 0.25 s) and the fields. finish 0.5 s, and half a
    second in the containers that nobody names."""
    spans = [
        _s("plan", t0, t0 + 0.5, 0),
        _s("read_all", t0 + 0.5, t0 + 10.0, op),
        _s("split", t0 + 0.5, t0 + 9.0, op),
        _s("decode.all", t0 + 0.5, t0 + 2.5, op),
        _s("decode.file", t0 + 0.5, t0 + 2.0, op, POOL),
        _s("read.join", t0 + 2.5, t0 + 3.5, op),
        _s("lanes.encode", t0 + 3.5, t0 + 4.0, op),
    ]
    if route == "planned":
        spans += [_s("merge.dispatch", t0 + 4.0, t0 + 5.0, op), _s("lanes.compress", t0 + 4.0, t0 + 4.25, op),
                  _s("merge.resolve", t0 + 5.0, t0 + 5.5, op), _s("agg.reduce", t0 + 5.5, t0 + 9.0, op)]
    else:
        spans += [_s("agg.reduce", t0 + 4.0, t0 + 9.0, op), _s("lanes.compress", t0 + 4.0, t0 + 4.25, op)]
    spans += [_s("agg.field", t0 + 6.0 + i, t0 + 6.5 + i, op) for i in range(3)]
    return spans + [_s("finish", t0 + 9.0, t0 + 9.5, op)]


def _hand_made(monkeypatch, spans, ops, counters=None):
    import ingest_spans
    import program_spans

    program = ProgramSpans(list(spans), (0.0, 1e9))
    monkeypatch.setattr(program_spans, "load", lambda path: program)
    monkeypatch.setattr(ingest_spans, "load", lambda path: ingest_spans.IngestSpans(list(spans), ops, (0.0, 1e9)))
    before, after = counters or ({}, {})
    plan = [(0.0, 1.0, "jit_merge_plan/sort.1:u32[16]")]  # the planned route's sort ran on the device
    return types.SimpleNamespace(trace=types.SimpleNamespace(path="hand-made", devices={0: plan}),
                                 counters_before=before, counters_after=after)


def _read_all(w):
    return {name: run.load_module("layer_metrics", name).read(w) for name in AGG_METRICS}


COUNTED = ({"aggregate": {"merges": 2, "rows_in": 10, "rows_out": 4, "fields": 28, "fields_on_host": 8}},
           {"aggregate": {"merges": 5, "rows_in": 100, "rows_out": 40, "fields": 70, "fields_on_host": 20}})


@pytest.mark.parametrize("route", ["planned", "fused"])
def test_the_agg_readers_on_hand_made_spans(monkeypatch, route):
    spans = [s for t, o in ((100.0, 1), (200.0, 2), (300.0, 3)) for s in one_read(t, o, route)]
    w = _hand_made(monkeypatch, spans, [(t, t + 10.0, READER) for t in (100.0, 200.0, 300.0)], COUNTED)
    merge, reduce = (2000.0, 3500.0) if route == "planned" else (500.0, 5000.0)
    assert _read_all(w) == {"agg_read_ms_p50": pytest.approx(3000.0), "agg_merge_ms_p50": pytest.approx(merge),
                            "agg_reduce_ms_p50": pytest.approx(reduce), "agg_host_field_share": pytest.approx(12 / 42),
                            # of 10 s: 0.5 s of read_all and split that no leaf covers
                            "agg_unattributed_share": pytest.approx(0.05)}


def test_a_program_without_the_aggregation_spans_reads_0_and_a_trace_without_operations_nothing(monkeypatch):
    # the parent's planned route: the sort's spans, and no agg.reduce, agg.field or aggregate{...}
    spans = [s for s in one_read(100.0, 1) if not s.name.startswith("agg.")]
    w = _hand_made(monkeypatch, spans, [(100.0, 110.0, READER)])
    values = _read_all(w)
    assert values["agg_reduce_ms_p50"] == 0.0 and values["agg_host_field_share"] is None
    assert values["agg_read_ms_p50"] == pytest.approx(3000.0) and values["agg_merge_ms_p50"] == pytest.approx(2000.0)
    assert 0 < values["agg_unattributed_share"] < 1
    w = _hand_made(monkeypatch, [], [])
    assert _read_all(w) == dict.fromkeys(AGG_METRICS)
    w.trace.devices = {0: [(0.0, 1.0, "jit_dedup_select/sort.1:u32[16]")]}
    assert run.load_module("layer_metrics", "agg_host_field_share").read(w) == 0.0  # a dedup read merges nothing here


def test_the_roofline_reader_counts_the_aggregation_programs_alone():
    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))
    kind = "TPU v5 lite"
    device = [(0.0, 0.5, "jit_merge_plan/sort.1:u32[16]"), (0.25, 1.0, "jit_agg_sum/scatter.2"),
              (1.0, 1.25, "jit_agg_pick/x"), (2.0, 3.0, "jit_dedup_select/sort.2:u32[16]"),
              (4.0, 4.25, "jit_fused_aggregate/y"), (5.0, 6.0, "jit_fused_partial_update/z")]
    before = {"aggregate": {"merges": 1, "rows_in": 100, "rows_out": 40, "fields": 14}}
    after = {"aggregate": {"merges": 3, "rows_in": 12_500_100, "rows_out": 5_000_040, "fields": 42}}
    w = types.SimpleNamespace(trace=types.SimpleNamespace(devices={0: device}), peaks=peaks, device_kind=kind,
                              counters_before=before, counters_after=after)
    reader = run.load_module("layer_metrics", "agg_roofline")
    want = 100.0 * ref.merge_bytes(12_500_000, 5_000_000, 14) / peaks[kind]["hbm_bytes_per_s"] / 1.5
    assert reader.read(w) == pytest.approx(want)
    assert ref.merge_bytes(10, 4, 14) == 10 * (17 + 2) + 4 * 14 * 8
    w.counters_after = before
    assert reader.read(w) is None  # the programs ran, and the window counts no aggregation merge
    w.counters_before, w.counters_after = {}, {}
    assert reader.read(w) is None  # a program that counts none: the parent's
    w.trace.devices = {0: device[3:4]}
    assert reader.read(w) == 0.0  # no aggregation program on the device: a dedup read
