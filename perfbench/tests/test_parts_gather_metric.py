"""The reader of a rewrite's gather from parts (PR 39): `fc_parts_gather_share`,
`compaction{rows_gathered_from_parts}` / `compaction{rows_gathered}` over the
window, and what it reads of a program that counts no such cell.

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests -q -p no:cacheprovider
"""

import json
import os
import types

import run

CELLS = ["cdc-upsert.zipf-ingest", "dedicated-compact-10m.full-compaction"]


def _read(before, after):
    read = run.load_module("layer_metrics", "fc_parts_gather_share").read
    return read(types.SimpleNamespace(counters_before=before, counters_after=after))


def test_the_share_of_cells_a_rewrite_took_from_the_parts():
    before = {"compaction": {"rows_gathered": 1_700, "rows_gathered_from_parts": 1_000, "rows_out": 100}}
    after = {"compaction": {"rows_gathered": 18_700, "rows_gathered_from_parts": 11_000, "rows_out": 1_100}}
    assert _read(before, after) == 10_000 / 17_000
    # the counters started in the window: nothing before it
    assert _read({}, {"compaction": {"rows_gathered": 170, "rows_gathered_from_parts": 100}}) == 100 / 170


def test_a_window_that_took_nothing_from_the_parts_reads_zero():
    shortcut = {"compaction": {"rows_in": 100, "rows_gathered": 700, "rounds": 3}}  # keys already increasing: no parts counter
    assert _read({}, shortcut) == 0.0
    both = {"compaction": {"rows_in": 100, "rows_gathered": 700, "rows_gathered_from_parts": 0}}
    assert _read(both, both) == 0.0  # a window that rewrote nothing
    # a process that never rewrote (a merge-read of a write-only table): nothing was gathered, by any program
    assert _read({}, {"read": {"rows_gathered": 17}}) == 0.0  # the read path's counters are not a rewrite's
    assert _read({}, {"compaction": {"rounds": 2}}) == 0.0
    assert _read({}, {}) == 0.0


def test_a_program_that_rewrites_and_counts_no_gathered_cell_reads_nothing():
    parent = {"compaction": {"rounds": 3, "rows_in": 10, "bytes_in": 99}}  # it rewrote, and counted no gathered cell
    assert _read({}, parent) is None
    assert _read(parent, {"compaction": {"rounds": 4, "rows_in": 20, "bytes_in": 198}}) is None


def test_the_metric_is_listed_for_the_two_writing_cells():
    by_name = {m["name"]: m for m in json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))["per_layer"]}
    share = by_name["fc_parts_gather_share"]
    assert share == {"name": "fc_parts_gather_share", "unit": "share", "better": "higher", "source": "program_counter",
                     "layer": "write path", "moves": "rows_per_s", "workloads": CELLS}
    assert list(by_name)[-1] == "fc_parts_gather_share"  # appended, nothing after it
