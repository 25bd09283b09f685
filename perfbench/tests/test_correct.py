"""What decides `correct`, proved to fail: the control (the reference with a
guarantee broken, in the program's place) and a run of the harness with the
timed path broken underneath. On the CPU at a size a test run can hold; the
device kernels are pinned as the repo's own tests pin them.

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests -q -p no:cacheprovider
"""

import json
import os
import sys

import numpy as np
import pytest

import reference
import run

BENCH, ROOT = run.HERE, run.ROOT

ROWS = 60_000
SCHEMA = json.load(open(os.path.join(BENCH, "configs", "tableread-1m.json")))["schema"]
RUN_COLUMNS = 4  # c1, c4, d1, d3: the columns of SCHEMA that depend on the writing run


def _failed(numbers):
    return [name for name, value, limit in numbers if value > limit]


def _as_output(cols):
    return {n: (v, np.ones(len(v), dtype=bool)) for n, v in cols.items()}


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 11])
def test_reference_passes_and_controls_fail(seed):
    runs, ids, home, win = reference.make_runs(seed, ROWS, 4, 0.2)
    assert sum(len(r) for r in runs) == ROWS
    want = {n: (v, None) for n, v in reference.winners(ids, home, win, SCHEMA).items()}
    assert len(want) == 15
    assert _failed(reference.compare(_as_output(reference.winners(ids, home, win, SCHEMA)), want)) == []
    first = reference.compare(_as_output(reference.control_first_writer(ids, home, win, SCHEMA)), want)
    assert _failed(first) == ["wrong_cells"]
    assert dict((n, v) for n, v, _ in first)["wrong_cells"] == RUN_COLUMNS * (ROWS - len(ids))  # of every rewritten key
    stale = reference.compare(_as_output(reference.control_stale_snapshot(ids, home, win, 4, SCHEMA)), want)
    assert {"rows_out_minus_reference", "keys_missing", "wrong_cells"} <= set(_failed(stale))


def test_compare_counts_each_kind_of_fault():
    _, ids, home, win = reference.make_runs(5, 6_000, 4, 0.2)
    good = reference.winners(ids, home, win, SCHEMA)
    want = {n: (v, None) for n, v in good.items()}

    def numbers(mutate):
        out = {n: (v.copy(), np.ones(len(v), dtype=bool)) for n, v in good.items()}
        out = mutate(out) or out
        return {n: v for n, v, _ in reference.compare(out, want)}

    def one_cell(out):
        out["s1"][0][17] = "val-xxxx"

    def one_null(out):
        out["d2"][1][3] = False

    def duplicate(out):
        return {n: (np.concatenate([v, v[:1]]), np.concatenate([m, m[:1]])) for n, (v, m) in out.items()}

    def shuffled(out):
        order = np.random.default_rng(0).permutation(len(ids))
        return {n: (v[order], m[order]) for n, (v, m) in out.items()}

    def dropped(out):
        return {n: (v[1:], m[1:]) for n, (v, m) in out.items()}

    assert numbers(one_cell)["wrong_cells"] == 1
    assert numbers(one_null)["null_cells"] == 1
    assert numbers(duplicate)["keys_duplicated"] == 1
    assert numbers(dropped)["keys_missing"] == 1
    assert all(v == 0 for v in numbers(shuffled).values())


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout's worth of benchmark data at a test's size: BENCHMARK.json
    with one cell, its configuration a cut of tableread-1m."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    config = json.load(open(os.path.join(BENCH, "configs", "tableread-1m.json")))
    config.update(name="tiny", rows=ROWS, table="bench.tiny")
    os.makedirs(tmp_path / "configs")
    json.dump(config, open(tmp_path / "configs" / "tiny.json", "w"))
    bench["configs"] = [dict(bench["configs"][0], name="tiny", file="configs/tiny.json")]
    bench["workloads"] = [dict(bench["workloads"][0], name="tiny.merge-read", config="tiny")]
    # the peaks table has no CPU, and an unknown device kind is an error, not a default
    bench["per_layer"] = [m for m in bench["per_layer"] if m["name"] != "merge_roofline"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))
    return str(tmp_path)


def test_a_run_is_correct_and_reports_every_metric(tiny_root):
    result = run.run_cell("tiny.merge-read", 2**31 + 5, 0.5, False, need_chip=False, root=tiny_root)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"rows_per_s", "setup_s"}
    assert list(result)[-1] == "compared" and all(v["value"] == 0 for v in result["compared"].values())
    traced = run.run_cell("tiny.merge-read", 7, 0.5, True, need_chip=False, root=tiny_root)
    assert traced["correct"] is True
    bench = json.load(open(os.path.join(tiny_root, "BENCHMARK.json")))
    names = {m["name"] for m in bench["per_layer"]}
    # the CPU backend keeps no memory_stats, and half a second holds under 20 operations:
    # those readers have nothing to read and their metrics are left out
    assert set(traced["metrics"]) == names - {"device_peak_bytes", "op_ms_p90"}
    assert traced["metrics"]["compiles_in_window"]["value"] == 0
    assert 0 < traced["device"]["busy_s"] <= traced["device"]["window_s"]
    assert 0 < traced["metrics"]["device_idle_share"]["value"] < 1
    assert traced["breakdown"]["device_ops"] and traced["breakdown"]["idle_gaps"]


def test_an_answer_altered_where_it_is_produced_is_not_correct(tiny_root, monkeypatch):
    """The harness past its look for a chip, with the timed path broken
    underneath: the merge-read returns one cell of one row altered."""
    from paimon_tpu.table.read import TableRead

    honest = TableRead.read_all

    def altered(self, splits):
        out = honest(self, splits)
        np.asarray(out.column("c1").values)[len(out.column("c1").values) // 2] += 1
        return out

    monkeypatch.setattr(TableRead, "read_all", altered)
    result = run.run_cell("tiny.merge-read", 3, 0.3, False, need_chip=False, root=tiny_root)
    assert result["correct"] is False
    assert result["compared"]["wrong_cells"] == {"value": 1, "limit": 0}


def test_half_of_the_rows_left_out_is_not_correct(tiny_root, monkeypatch):
    """The same, with the merge-read returning the first half of its rows."""
    from paimon_tpu.table.read import TableRead

    honest = TableRead.read_all
    monkeypatch.setattr(TableRead, "read_all", lambda self, splits: (lambda out: out.slice(0, out.num_rows // 2))(
        honest(self, splits)))
    result = run.run_cell("tiny.merge-read", 4, 0.3, False, need_chip=False, root=tiny_root)
    assert result["correct"] is False
    assert result["compared"]["keys_missing"]["value"] > 0 and result["compared"]["wrong_cells"]["value"] == 0
    assert result["compared"]["operations_with_wrong_row_count"]["value"] == result["attempted"]


def test_roofline_reader_needs_busy_time_and_a_known_device():
    import types

    reader = run.load_module("layer_metrics", "merge_roofline")
    peaks = run.load_json(BENCH, "peaks.json")
    w = types.SimpleNamespace(busy_s={0: 0.5}, rows=10_000_000, device_kind="TPU v5 lite", peaks=peaks)
    assert reader.read(w) == pytest.approx(100 * (10_000_000 * 20 / 819e9) / 0.5)
    w.busy_s = {}
    assert reader.read(w) is None  # nothing to read is no number, never 0
    w.busy_s, w.device_kind = {0: 0.5}, "cpu"
    with pytest.raises(KeyError):
        reader.read(w)


def test_no_chip_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        run.run_cell("tableread-1m.merge-read", 1, 0.1, False)
    assert e.value.code not in (0, None) and "no TPU" in str(e.value.code)
    assert capsys.readouterr().out == ""
