"""The `full_compaction` op and its yardstick, proved to fail where they must:
the cell is `correct`; a compaction that keeps first writers and a job that
commits nothing are not; a layout fault or a touched base counts an
operation for nothing; the base outlives its clones. On the CPU at a size a
test run can hold; the device kernels are pinned as the repo's own tests pin
them.

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests -q -p no:cacheprovider
"""

import json
import os
import types

import numpy as np
import pytest

import reference
import reference_compaction
import run
from ingest_spans import IngestSpans
from program_spans import Span

BENCH, ROOT = run.HERE, run.ROOT
CELL = "dedicated-compact-10m.full-compaction"
ROWS = 60_000
RUN_COLUMNS = 4  # c1, c4, d1, d3: the columns of the schema that depend on the writing run
CONFIG = {**json.load(open(os.path.join(BENCH, "configs", "dedicated-compact-10m.json"))), "name": "tinyfc",
          "rows": ROWS, "table": "bench.tinyfc"}


def _failed(numbers):
    return [name for name, value, limit in numbers if value > limit]


def _value(numbers, name):
    return {n: v for n, v, _ in numbers}[name]


@pytest.fixture
def tiny_root(tmp_path):
    """BENCHMARK.json with the one cell, its configuration cut to ROWS."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    os.makedirs(tmp_path / "configs")
    json.dump(CONFIG, open(tmp_path / "configs" / "tinyfc.json", "w"))
    entry = next(c for c in bench["configs"] if c["name"] == "dedicated-compact-10m")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    bench["configs"] = [dict(entry, name="tinyfc", file="configs/tinyfc.json")]
    bench["workloads"] = [dict(cell, name="tinyfc.full-compaction", config="tinyfc")]
    # the cell's own metrics and the listless ones; the peaks table has no CPU
    bench["per_layer"] = [dict(m, workloads=["tinyfc.full-compaction"]) for m in bench["per_layer"]
                          if m.get("workloads", [CELL]) == [CELL] and m["name"] != "merge_roofline"]
    json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))
    return str(tmp_path)


@pytest.fixture
def op(tmp_path):
    return run.load_module("ops", "full_compaction").Op(CONFIG, 2**31 + 7, str(tmp_path), run.Spans())


def test_the_cell_is_correct_and_its_traced_line_carries_every_metric(tiny_root):
    result = run.run_cell("tinyfc.full-compaction", 2**31 + 5, 0.5, False, need_chip=False, root=tiny_root)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"rows_per_s", "setup_s"}
    assert len(result["compared"]) == 8 and all(v == {"value": 0, "limit": 0} for v in result["compared"].values())
    traced = run.run_cell("tinyfc.full-compaction", 11, 0.5, True, need_chip=False, root=tiny_root)
    assert traced["correct"] is True
    names = {m["name"] for m in json.load(open(os.path.join(tiny_root, "BENCHMARK.json")))["per_layer"]}
    assert len([n for n in names if n.startswith("fc_")]) == 9 and len(names) == 9 + 7
    assert set(traced["metrics"]) == names - {"device_peak_bytes"}  # the CPU backend keeps no memory_stats
    value = {n: m["value"] for n, m in traced["metrics"].items()}
    assert value["compiles_in_window"] == 0 and value["data_cache_hit_share"] == 0.0
    assert value["fc_tiles_per_merge"] == 1.0  # 60,000 rows: one sort, under a stream tile
    assert 5 < value["fc_bytes_out_per_row"] < 200
    for name in ("fc_clone_ms_p50", "fc_read_ms_p50", "fc_merge_ms_p50", "fc_gather_ms_p50", "fc_file_write_ms_p50",
                 "fc_commit_ms_p50"):
        assert 0 < value[name] < value["op_ms_p50"], name
    assert 0 <= value["fc_unattributed_share"] < 0.5


def test_first_writer_wins_is_not_correct_and_the_base_outlives_three_operations(op):
    results = [op() for _ in range(3)]
    rows_out = [op.rows_of(r) for r in results]
    winners = len(op.ids)
    assert rows_out == [winners] * 3 and [r[0] for r in results] == [f"bench.tinyfc_clone_{i}" for i in (1, 2, 3)]
    assert not os.path.exists(op.catalog.table_path(results[1][0]))  # the clone before the last is dropped
    numbers = run._compare(op, results[-1], rows_out)
    assert [n for n, _, _ in numbers] == ["operations_with_wrong_row_count", "rows_out_minus_reference", "keys_missing",
                                          "keys_invented", "keys_duplicated", "columns_missing", "null_cells",
                                          "wrong_cells"]
    assert _failed(numbers) == [] and all(limit == 0 for _, _, limit in numbers)
    got = op.output_columns(results[-1])
    first = reference.compare(got, {n: (v, None) for n, v in reference_compaction.control_first_writer(
        op.ids, op.home, op.winner_run, CONFIG["schema"]).items()})
    assert _failed(first) == ["wrong_cells"] and _value(first, "wrong_cells") == RUN_COLUMNS * (ROWS - winners)
    # the base: its snapshot, its thirteen-files-at-full-size list and its merge-read are what set-up left
    outcome = results[-1][1]
    assert outcome.base_snapshot == op.base_snapshot == 4 and outcome.base_files == op.base_files
    assert all(level == 0 for level, *_ in op.base_files) and sum(f[3] for f in op.base_files) == ROWS
    rb = op.table.new_read_builder()
    base = {n: (np.asarray(c.values), c.valid_mask()) for n, c in
            ((n, rb.new_read().read_all(rb.new_scan().plan()).column(n)) for n, _ in CONFIG["schema"][:3])}
    want = {n: v for n, v in op.reference_columns().items() if n in base}
    assert _failed(reference.compare(base, want)) == []
    # the compacted clone: one snapshot more, every file at the top level, none of the inputs
    assert outcome.snapshots == ((5, "COMPACT"),) and outcome.start_snapshot == 4
    assert {level for level, *_ in outcome.live} == {5} and not set(outcome.inputs) & {f[4] for f in outcome.live}


def test_a_job_that_commits_nothing_counts_for_no_rows_and_is_not_correct(tiny_root, monkeypatch):
    from paimon_tpu.table.write import TableCommit

    honest = TableCommit.commit_messages
    # the loads of set-up land; a commit that carries a compaction is acknowledged and lands nothing
    monkeypatch.setattr(TableCommit, "commit_messages", lambda self, identifier, messages: (
        [] if any(m.compact_before for m in messages) else honest(self, identifier, messages)))
    result = run.run_cell("tinyfc.full-compaction", 5, 0.3, False, need_chip=False, root=tiny_root)
    assert result["correct"] is False
    assert result["compared"]["operations_with_wrong_row_count"]["value"] == result["attempted"] >= 1
    assert result["compared"]["wrong_cells"]["value"] == 0  # the clone still reads its runs: the answer stands


def test_each_fault_of_the_layout_makes_an_operation_count_for_nothing(op):
    name, whole = op()
    winners, base = len(op.ids), (op.base_snapshot, op.base_files)
    rows = lambda outcome: op.rows_of((name, outcome))  # noqa: E731
    assert rows(whole) == winners and reference_compaction.faults(whole, *base) == []
    level, lo, hi, count, file_name = whole.live[0]
    overlapping = whole._replace(live=whole.live + ((level, hi, hi, 1, "data-extra.orc"),))
    below = whole._replace(live=((level - 1, lo, hi, count, file_name),) + whole.live[1:])
    touched = whole._replace(base_snapshot=whole.base_snapshot + 1)
    fewer = whole._replace(base_files=whole.base_files[1:])
    kept = whole._replace(live=whole.live + ((level, (10**12,), (10**12,), 1, whole.inputs[0]),))
    for broken, why in ((overlapping, "overlap"), (below, "below the top level"), (touched, "base table was touched"),
                        (fewer, "base table was touched"), (kept, "input files still live"),
                        (whole._replace(returned=False), "did not say"), (whole._replace(live=()), "no live data file"),
                        (whole._replace(snapshots=((5, "APPEND"),)), "landed is"),
                        (whole._replace(snapshots=whole.snapshots + ((6, "COMPACT"),)), "2 snapshots"),
                        (reference_compaction.control_commits_nothing(whole, op.base_files), "0 snapshots")):
        assert rows(broken) == 0, why
        assert any(why in fault for fault in reference_compaction.faults(broken, *base)), why


def test_a_clone_links_the_data_files_and_copies_the_metadata(op):
    name, outcome = op()
    base_dir, clone_dir = op.table.path, op.catalog.table_path(name)
    inputs = sorted(f[4] for f in op.base_files)
    assert sorted(outcome.inputs) == inputs
    for file_name in inputs:  # immutable, so linked: one inode, two names
        mine, theirs = (os.stat(os.path.join(d, "bucket-0", file_name)) for d in (clone_dir, base_dir))
        assert mine.st_ino == theirs.st_ino and mine.st_nlink == 2
    written = [f[4] for f in outcome.live]
    assert written and all(os.stat(os.path.join(clone_dir, "bucket-0", f)).st_nlink == 1 for f in written)
    assert not any(os.path.exists(os.path.join(base_dir, "bucket-0", f)) for f in written)
    for hint, base_value in (("LATEST", "4"), ("EARLIEST", "1")):  # the program overwrites them: copied
        mine, theirs = (os.path.join(d, "snapshot", hint) for d in (clone_dir, base_dir))
        assert os.stat(mine).st_ino != os.stat(theirs).st_ino and os.stat(theirs).st_nlink == 1
        assert open(theirs).read() == base_value
    assert open(os.path.join(clone_dir, "snapshot", "LATEST")).read() == "5"
    for folder in ("schema", "manifest", "snapshot"):
        for f in os.listdir(os.path.join(base_dir, folder)):
            assert os.stat(os.path.join(base_dir, folder, f)).st_nlink == 1


# ---- the cell's readers on hand-made spans --------------------------------------

CLIENT, WORKER = (0, 0), (0, 1)


def s(name, start, end, line=CLIENT):
    return Span(name, start, end, line, {"op": 1})


def one_round(t0):
    """An operation of 20 s: the clone 0.5 s; `compact` 17.5 s holding the
    pick, the wait for the read head (4 s, `compact.read` on a worker), lanes
    0.5 s, dispatch 1 s holding a lane compression, resolve 0.5 s, gather
    3 s, half a second nobody names and two files of 4 s; commit 1 s; half a
    second of the benchmark's look at the result under `plan` spans."""
    return [
        s("compact", t0 + 0.5, t0 + 18.0),
        s("compact.pick", t0 + 0.5, t0 + 0.75),
        s("pipeline.compact.wait", t0 + 0.75, t0 + 4.75),
        s("compact.read", t0 + 0.75, t0 + 4.75, WORKER),
        s("lanes.encode", t0 + 4.75, t0 + 5.25),
        s("merge.dispatch", t0 + 5.25, t0 + 6.25),
        s("lanes.compress", t0 + 5.25, t0 + 5.5),
        s("merge.resolve", t0 + 6.25, t0 + 6.75),
        s("gather", t0 + 6.75, t0 + 9.75),
        s("file.write", t0 + 10.0, t0 + 14.0),
        s("file.write", t0 + 14.0, t0 + 18.0),
        s("commit", t0 + 18.5, t0 + 19.5),
        s("plan", t0 + 19.5, t0 + 19.75),
    ]


def test_the_cells_readers_on_hand_made_spans(monkeypatch):
    spans = IngestSpans(one_round(100.0) + one_round(200.0), [(100.0, 120.0, CLIENT), (200.0, 220.0, CLIENT)], None)
    w = types.SimpleNamespace(trace=types.SimpleNamespace(path="hand-made", spans={"clone": [(200.0, 200.5), (100.0, 100.5)]}),
                              span_s=lambda name: {"clone": [0.5, 0.25, 0.75]}.get(name, []))
    import ingest_spans

    monkeypatch.setattr(ingest_spans, "load", lambda path: spans)
    values = {}
    for name in ("fc_clone_ms_p50", "fc_read_ms_p50", "fc_merge_ms_p50", "fc_gather_ms_p50", "fc_file_write_ms_p50",
                 "fc_commit_ms_p50", "fc_unattributed_share"):
        module = run.load_module("layer_metrics", name)
        if hasattr(module, "load"):
            monkeypatch.setattr(module, "load", lambda path: spans)
        values[name] = module.read(w)
    assert values == {"fc_clone_ms_p50": 500.0, "fc_read_ms_p50": 4000.0, "fc_merge_ms_p50": 2000.0,
                      "fc_gather_ms_p50": 3000.0, "fc_file_write_ms_p50": 8000.0, "fc_commit_ms_p50": 1000.0,
                      # of an operation's 19.5 s outside the clone: 0.25 s before the files, 0.5 s before the
                      # commit and 0.25 s after the last plan lie under no naming span
                      "fc_unattributed_share": pytest.approx(1.0 / 19.5)}
    bare = types.SimpleNamespace(trace=types.SimpleNamespace(path="bare", spans={}), span_s=lambda name: [])
    nothing = IngestSpans([], [], None)
    for name in ("fc_merge_ms_p50", "fc_unattributed_share", "fc_clone_ms_p50"):
        module = run.load_module("layer_metrics", name)
        if hasattr(module, "load"):
            monkeypatch.setattr(module, "load", lambda path: nothing)
        assert module.read(bare) is None  # no pb:op, no pb:clone: nothing to read
    bare.span_s = lambda name: {"op": [1.0]}.get(name, [])
    assert run.load_module("layer_metrics", "fc_clone_ms_p50").read(bare) == 0.0  # operations that cloned nothing


def test_the_counter_readers_and_a_program_without_the_counters():
    before = {"merge": {"tiles": 10, "merges": 2}, "compaction": {"bytes_out": 1_000, "rows_out": 100}}
    after = {"merge": {"tiles": 298, "merges": 5}, "compaction": {"bytes_out": 81_000, "rows_out": 2_100}}
    w = types.SimpleNamespace(counters_before=before, counters_after=after)
    assert run.load_module("layer_metrics", "fc_tiles_per_merge").read(w) == 96.0
    assert run.load_module("layer_metrics", "fc_bytes_out_per_row").read(w) == 40.0
    parent = types.SimpleNamespace(counters_before={}, counters_after={"read": {"ops": 3}})
    assert run.load_module("layer_metrics", "fc_tiles_per_merge").read(parent) is None
    assert run.load_module("layer_metrics", "fc_bytes_out_per_row").read(parent) == 0.0  # a window that rewrote no row
