"""The reader of the aggregation's scan share: `agg_scan_field_share`,
`aggregate{fields_by_scan}` / `aggregate{fields}` over the window, what it
reads of a program that does not count the scans, and what it reads in a
tiny traced `aggregate_read` run.

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests -q -p no:cacheprovider
"""

import json
import os
import types

import run

CELL = "aggregation-6m.rollup-read"
PLAN = [(0.0, 1.0, "jit_merge_plan/sort.1:u32[16]")]


def _read(before, after, devices=None):
    read = run.load_module("layer_metrics", "agg_scan_field_share").read
    trace = types.SimpleNamespace(devices={0: PLAN} if devices is None else devices)
    return read(types.SimpleNamespace(trace=trace, counters_before=before, counters_after=after))


def test_the_share_of_fields_reduced_by_scan():
    before = {"aggregate": {"merges": 2, "fields": 28, "fields_on_host": 8, "fields_by_scan": 20}}
    after = {"aggregate": {"merges": 5, "fields": 70, "fields_on_host": 20, "fields_by_scan": 50}}
    assert _read(before, after) == 30 / 42
    # the counters started in the window: nothing before it
    assert _read({}, {"aggregate": {"merges": 1, "fields": 14, "fields_by_scan": 10}}) == 10 / 14


def test_a_program_that_counts_no_scan_reads_zero_and_one_that_counts_nothing_reads_nothing():
    parent = ({"aggregate": {"merges": 2, "fields": 28, "fields_on_host": 8}},
              {"aggregate": {"merges": 5, "fields": 70, "fields_on_host": 20}})
    assert _read(*parent) == 0.0  # the aggregation's counters without fields_by_scan
    assert _read({}, {}) is None  # the aggregation's programs ran, and nothing was counted
    assert _read({}, {}, devices={0: [(0.0, 1.0, "jit_dedup_select/sort.1:u32[16]")]}) == 0.0  # a dedup read


def test_the_metric_is_listed_for_the_aggregation_cell_alone():
    per_layer = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))["per_layer"]
    assert per_layer[-1] == {"name": "agg_scan_field_share", "unit": "share", "better": "higher",
                             "source": "program_counter", "layer": "aggregation path", "moves": "rows_per_s",
                             "workloads": [CELL]}


def test_a_tiny_traced_rollup_read_reads_the_share(tmp_path):
    bench = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    config = json.load(open(os.path.join(run.HERE, "configs", "aggregation-6m.json")))
    config.update(name="tinyscan", rows=60_000, keys=24_000, table="bench.tinyscan")
    os.makedirs(tmp_path / "configs")
    json.dump(config, open(tmp_path / "configs" / "tinyscan.json", "w"))
    entry = next(c for c in bench["configs"] if c["name"] == "aggregation-6m")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    bench["configs"] = [dict(entry, name="tinyscan", file="configs/tinyscan.json")]
    bench["workloads"] = [dict(cell, name="tinyscan.rollup-read", config="tinyscan")]
    bench["per_layer"] = [dict(bench["per_layer"][-1], workloads=["tinyscan.rollup-read"])]
    json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))
    traced = run.run_cell("tinyscan.rollup-read", 2**31 + 44, 0.5, True, need_chip=False, root=str(tmp_path))
    assert traced["correct"] is True
    # six BIGINT sums and four STRING picks of fourteen fields; the four DOUBLE maxima scatter
    assert traced["metrics"]["agg_scan_field_share"]["value"] == 10 / 14
