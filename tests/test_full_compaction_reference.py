"""The dedicated compaction job at a size a test can hold (perfbench's cell
`dedicated-compact-10m.full-compaction`): `DedicatedCompactor.run_once(full=True)`
over a write-only table against `perfbench/reference_compaction.py`, which
imports nothing of the program: the layout a full compaction must leave, and
the table's answer unchanged; the rewrite's read head as a span and a counter
(docs/tracing.md); the defaults the cell's configuration states.
"""

import json
import os
import sys

import numpy as np
import pytest

import paimon_tpu as pt
from paimon_tpu.catalog import FileSystemCatalog
from paimon_tpu.metrics import registry
from paimon_tpu.ops import merge as M
from paimon_tpu.options import CoreOptions
from paimon_tpu.table.compactor import DedicatedCompactor

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perfbench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import reference  # noqa: E402  (perfbench/reference.py)
import reference_compaction  # noqa: E402
from reference_compaction import Outcome  # noqa: E402
from test_tracing import _inside, _named, traced  # noqa: E402

CONFIG = json.load(open(os.path.join(BENCH, "configs", "dedicated-compact-10m.json")))
SCHEMA = CONFIG["schema"]
TOP = CONFIG["program_defaults"]["num-levels"] - 1
RUN_COLUMNS = 4  # c1, c4, d1, d3: the columns of SCHEMA that depend on the writing run


def _row_type():
    fields = []
    for name, spec in SCHEMA:
        base, _, rest = spec.partition(" ")
        fields.append((name, getattr(pt, base)(rest != "NOT NULL")))
    return pt.RowType.of(*fields)


def _commit(table, columns, kinds=None):
    wb = table.new_batch_write_builder()
    w = wb.new_write()
    w.write(columns, kinds)
    wb.new_commit().commit(w.prepare_commit())
    w.close()


def _table(warehouse, seed, rows, **options):
    """The cell's table at `rows` input rows: overlapping sorted runs, a commit a run, write-only."""
    catalog = FileSystemCatalog(str(warehouse), commit_user="fc")
    table = catalog.create_table(CONFIG["table"], _row_type(), primary_keys=CONFIG["primary_keys"],
                                 options={**{k: str(v) for k, v in CONFIG["options"].items()}, **options})
    runs, ids, home, winner_run = reference.make_runs(seed, rows, CONFIG["runs"], CONFIG["rewrite_share"])
    for r, run_ids in enumerate(runs):
        _commit(table, reference.columns(run_ids, np.full(len(run_ids), r, dtype=np.int64), SCHEMA))
    return catalog, table, ids, home, winner_run


def _files(table):
    return tuple((f.level, tuple(f.min_key), tuple(f.max_key), f.row_count, f.file_name)
                 for split in table.new_read_builder().new_scan().plan() for f in split.files)


def _read_columns(table):
    rb = table.new_read_builder()
    out = rb.new_read().read_all(rb.new_scan().plan())
    return {n: (np.asarray(out.column(n).values), out.column(n).valid_mask()) for n, _ in SCHEMA}


def _failed(numbers):
    return [(name, value) for name, value, limit in numbers if value > limit]


def _run_job(catalog, table):
    """One round of the job, and what an observer reads off the table before and after it."""
    manager = table.store.snapshot_manager
    start, before = manager.latest_snapshot_id(), _files(table)
    returned = DedicatedCompactor(table).run_once(full=True)
    after = catalog.get_table(CONFIG["table"])
    landed = tuple((i, manager.snapshot(i).commit_kind.value) for i in range(start + 1, manager.latest_snapshot_id() + 1))
    # the job compacts the table in place: the table before the job stands in for the base it must not touch
    return Outcome(returned, start, landed, TOP + 1, _files(after), tuple(f[4] for f in before), start, before), before


CASES = {
    # name: (seed, input rows, table options over the configuration's)
    "four-overlapping-runs": (7, 40_000, {}),
    "a-run-of-deletes": (2**31 + 3, 40_000, {}),
    "several-output-files": (11, 60_000, {"target-file-size": "1 mb"}),
    "more-rows-than-a-stream-tile": (13, 150_000, {}),
    "the-snapshot-before-still-reads": (17, 40_000, {}),
}


@pytest.mark.parametrize("case", CASES)
def test_a_dedicated_full_compaction_against_the_plain_reference(tmp_path, case):
    seed, rows, options = CASES[case]
    catalog, table, ids, home, winner_run = _table(tmp_path / "warehouse", seed, rows, **options)
    assert table.options.write_only and {f[0] for f in _files(table)} == {0}  # the writers compacted nothing
    keep = np.ones(len(ids), dtype=bool)
    if case == "a-run-of-deletes":  # a fifth run retracts every seventh key: dropped at the top level
        keep = np.arange(len(ids)) % 7 != 0
        gone = ids[~keep]
        _commit(table, reference.columns(gone, np.full(len(gone), 4, dtype=np.int64), SCHEMA), kinds=["-D"] * len(gone))
    before_answer = _read_columns(table)
    registry.reset()
    outcome, inputs = _run_job(catalog, table)
    counters = registry.snapshot()
    registry.reset()

    # the layout: one COMPACT snapshot, every live file at the top level, one sorted run, no input left
    assert reference_compaction.faults(outcome, outcome.start_snapshot, inputs) == []
    assert reference_compaction.rows_if_whole(outcome, outcome.start_snapshot, inputs) == int(keep.sum())
    assert outcome.snapshots == ((outcome.start_snapshot + 1, "COMPACT"),)
    assert {f[0] for f in outcome.live} == {TOP} and not {f[4] for f in outcome.live} & set(outcome.inputs)
    after = catalog.get_table(CONFIG["table"])
    assert all(f.delete_row_count == 0 for s in after.new_read_builder().new_scan().plan() for f in s.files)
    # the answer: what it was, which is each key once from its last writer
    want = {n: (v[keep], None) for n, v in reference_compaction.table_after(ids, home, winner_run, SCHEMA).items()}
    got = _read_columns(after)
    assert _failed(reference.compare(got, want)) == [] and _failed(reference.compare(before_answer, want)) == []
    assert np.array_equal(got["id"][0], ids[keep])  # row for row: each key once, ascending
    first = {n: (v[keep], None) for n, v in reference_compaction.control_first_writer(ids, home, winner_run, SCHEMA).items()}
    rewritten_kept = int((home != winner_run)[keep].sum())
    assert _failed(reference.compare(got, first)) == [("wrong_cells", RUN_COLUMNS * rewritten_kept)]
    assert reference_compaction.rows_if_whole(
        reference_compaction.control_commits_nothing(outcome, inputs), outcome.start_snapshot, inputs) == 0
    # what the rewrite counted: every input row read, every winner written, the decoded bytes of its read head
    rewrite = counters["compaction"]
    assert rewrite["rows_in"] == sum(f[3] for f in inputs) and rewrite["rows_out"] == int(keep.sum())
    assert rewrite["files_out"] == len(outcome.live) and rewrite["bytes_in"] == counters["datafile"]["bytes_decoded"]
    assert rewrite["bytes_in"] > 100 * rewrite["rows_in"] and 0 < rewrite["bytes_out"] < rewrite["bytes_in"]

    if case == "several-output-files":
        ranges = [(lo, hi) for _, lo, hi, _, _ in outcome.live]
        assert len(ranges) >= 3 and ranges == sorted(ranges)  # in the order they were written: ascending
        assert all(a[1] < b[0] for a, b in zip(ranges, ranges[1:])) and all(lo <= hi for lo, hi in ranges)
    else:
        assert len(outcome.live) == 1
    if case == "more-rows-than-a-stream-tile":  # key-range tiles of one padded shape serve the compaction
        assert rows > M._STREAM_TILE_ROWS and counters["merge"]["merges"] == 1
        assert counters["merge"]["tiles"] == -(-rows * 5 // (M._STREAM_TILE_ROWS * 4)) == 2
        assert counters["merge"]["pad_rows"] == 2 * M._STREAM_TILE_ROWS - rows and counters["merge"]["winners"] == len(ids)
    else:
        assert counters["merge"]["tiles"] == counters["merge"]["merges"] == 1
    if case == "the-snapshot-before-still-reads":  # the job deletes no file: expiry's work
        bucket = os.path.join(after.path, "bucket-0")
        assert all(os.path.exists(os.path.join(bucket, name)) for name in outcome.inputs)
        pinned = after.copy({"scan.snapshot-id": str(outcome.start_snapshot)})
        assert _files(pinned) == inputs and _failed(reference.compare(_read_columns(pinned), want)) == []
        assert DedicatedCompactor(after).run_once(full=True) is False  # and a second round finds nothing to do


# ---- the rewrite's read head: a span, a counter, and the thread a round runs on -----

@pytest.fixture(scope="module")
def traced_job(tmp_path_factory):
    """One traced round of the job over 150,000 input rows (two stream tiles)."""
    tmp = tmp_path_factory.mktemp("fc")
    catalog, table, ids, _, _ = _table(tmp / "warehouse", 19, 150_000)
    registry.reset()
    with traced(tmp / "trace") as events:
        assert DedicatedCompactor(table).run_once(full=True) is True
    counters = registry.snapshot()
    registry.reset()
    return {"events": events, "counters": counters, "winners": len(ids), "files": len(_files(table))}


JOB_SPANS = ("plan", "compact", "compact.pick", "compact.read", "decode.file", "lanes.encode", "merge.dispatch",
             "merge.resolve", "gather", "file.write", "prepare_commit", "commit")


@pytest.mark.parametrize("name", JOB_SPANS)
def test_a_traced_round_of_the_job_opens_the_span(traced_job, name):
    assert _named(traced_job["events"], name), sorted({e[0] for e in traced_job["events"]})


def test_compact_read_times_the_fan_out_and_the_concat_and_counts_the_decoded_bytes(traced_job):
    events, counters = traced_job["events"], traced_job["counters"]
    (round_,), (read,) = _named(events, "compact"), _named(events, "compact.read")
    assert _inside(read, round_) and read[4]["parent"] == "compact" and read[4]["op"] == round_[4]["op"]
    decoded = _named(events, "decode.file")
    assert read[4]["files"] == len(decoded) == 4 and read[4]["rows"] == round_[4]["rows_in"] == 150_000
    assert read[4]["bytes"] == sum(e[4]["bytes"] for e in decoded) == counters["compaction"]["bytes_in"]
    # the pool's decode.file spans stay as they are: on their own threads, inside the read head, the round's operation
    assert all(read[1] <= e[1] and e[2] <= read[2] and e[4]["op"] == round_[4]["op"] and e[4]["pass"] == "all"
               for e in decoded)
    assert any(e[3] != read[3] for e in decoded)
    assert read[2] > max(e[2] for e in decoded)  # the join of the decoded parts follows the last decode, inside the span
    # the merge follows the read head, and its dispatch span says how many stream tiles served it
    (dispatch,) = _named(events, "merge.dispatch")
    assert dispatch[1] >= read[2] and _inside(dispatch, round_)
    assert dispatch[4]["tiles"] == counters["merge"]["tiles"] == 2 and dispatch[4]["rows"] == 150_000
    assert dispatch[4]["pad_rows"] == 2 * M._STREAM_TILE_ROWS - 150_000
    (gather,) = _named(events, "gather")
    assert gather[4]["rows_in"] == 150_000 and gather[4]["rows_out"] == traced_job["winners"]
    assert round_[4]["full"] == 1 and round_[4]["level_out"] == TOP and round_[4]["rows_out"] == traced_job["winners"]


def test_a_dedicated_jobs_round_runs_on_the_callers_thread(traced_job):
    events = traced_job["events"]
    (round_,), (prepare,), (commit,) = (_named(events, n) for n in ("compact", "prepare_commit", "commit"))
    assert round_[3] == prepare[3] == commit[3]  # no flush worker, no pipeline thread: the caller's
    assert round_[2] <= prepare[1] and prepare[2] <= commit[1] and commit[4]["snapshots"] == 1
    for name in ("compact.pick", "compact.read", "lanes.encode", "merge.dispatch", "merge.resolve", "gather", "file.write"):
        assert all(_inside(e, round_) for e in _named(events, name)), name
    # on that thread next to nothing of a round lies under no span that names its work
    named = sorted((e[1], e[2]) for e in events if e[3] == round_[3] and e[0] != "compact" and _inside(e, round_))
    covered, at = 0, round_[1]
    for start, end in named:
        covered += max(0, end - max(start, at))
        at = max(at, end)
    assert covered >= 0.9 * (round_[2] - round_[1])


# ---- the configuration ---------------------------------------------------------------

@pytest.mark.parametrize("option", [CoreOptions.TARGET_FILE_SIZE, CoreOptions.NUM_LEVELS, CoreOptions.FILE_COMPRESSION,
                                    CoreOptions.NUM_SORTED_RUNS_COMPACTION_TRIGGER, CoreOptions.NUM_SORTED_RUNS_STOP_TRIGGER],
                         ids=lambda o: o.key)
def test_the_configuration_states_a_default_of_the_program_and_does_not_set_it(option):
    stated = CONFIG["program_defaults"][option.key]
    resolved = CoreOptions({k: str(v) for k, v in CONFIG["options"].items()})
    held = {"target-file-size": resolved.target_file_size, "num-levels": resolved.num_levels,
            "file.compression": resolved.file_compression,
            "num-sorted-run.compaction-trigger": resolved.num_sorted_runs_compaction_trigger,
            "num-sorted-run.stop-trigger": resolved.num_sorted_runs_stop_trigger}[option.key]
    assert held == (128 << 20 if option.key == "target-file-size" else stated)
    assert option.key not in CONFIG["options"] and option.key not in CONFIG["job"]


def test_the_configuration_is_the_read_cells_table_under_the_jobs_guarantees():
    read = json.load(open(os.path.join(BENCH, "configs", "tableformat-10m.json")))
    for key in ("schema", "primary_keys", "rows", "winners", "runs", "rewrite_share", "options"):
        assert CONFIG[key] == read[key], key
    assert CONFIG["assumed"] == {k: read["assumed"][k] for k in ("schema", "runs", "keys")}
    assert CONFIG["options"]["write-only"] == "true" and CONFIG["job"]["write-only"] == "false"
    assert sorted(CONFIG["reduced"]) == sorted(CONFIG["reduced_why"]) == ["cluster", "clustering", "rewrite_share", "rows",
                                                                        "runs"]
    assert CONFIG["source_rows"] // CONFIG["source_buckets"] == CONFIG["source_rows_per_bucket"] == 15_625_000
    assert len(CONFIG["guarantees"]) == 6 and len(CONFIG["source"]) <= 200
    traffic = json.load(open(os.path.join(BENCH, "traffic", "full-compaction.json")))
    assert traffic["op"] == "full_compaction" and traffic["clients"] == 1 and traffic["loop"] == "closed"
    assert (traffic["warmup_ops_min"], traffic["warmup_ops_max"]) == (2, 3)
