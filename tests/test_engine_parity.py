"""Sort-engine parity: the device engine (xla-segmented) == the numpy oracle,
bit for bit, across seeds x key shapes x null rates x lane-compression on/off
x dict-domain on/off — at the kernel seams (dedup select, merge plan, fused
partial-update, fused aggregate, sorted_segments' keep-last mask) and at the
table level (reads, compaction, sort-compact). Also the rules that choose an
engine: effective_sort_engine, the SQL reduce's _engine_for, and the errors
for values the options do not accept."""

import types

import jax
import numpy as np
import pytest

from paimon_tpu.core.mergefn import _numpy_dedup_select
from paimon_tpu.ops import merge as M
from paimon_tpu.ops.merge import deduplicate_resolve, deduplicate_select, deduplicate_select_async, merge_plan
from paimon_tpu.options import SortEngine
from paimon_tpu.types import RowKind


def _dedup_oracle(lanes: np.ndarray, seq_lanes: np.ndarray | None = None) -> np.ndarray:
    return _numpy_dedup_select(lanes, seq_lanes, compress=False)


def _rand_lanes(rng, n, shape):
    """Key-lane matrices covering the shapes the planner narrows/packs
    differently: single dense, two mixed-width, four wide, u16-range."""
    if shape == "one":
        return rng.integers(0, max(2, n // 2), (n, 1)).astype(np.uint32)
    if shape == "narrow":
        return rng.integers(0, 200, (n, 1)).astype(np.uint32)
    if shape == "two":
        a = rng.integers(0, 50, n).astype(np.uint32)
        b = rng.integers(0, 1 << 20, n).astype(np.uint32)
        return np.stack([a, b], axis=1)
    a = rng.integers(0, 9, n).astype(np.uint32)
    b = rng.integers(0, 3, n).astype(np.uint32)
    c = rng.integers(0, 1 << 30, n).astype(np.uint32)
    d = rng.integers(0, 100, n).astype(np.uint32)
    return np.stack([a, b, c, d], axis=1)


def _segments(lanes: np.ndarray, seq: np.ndarray | None = None):
    """The numpy plan: the stable lexicographic order of (key lanes, seq
    lanes) and, in that order, each row's segment (equal key lanes) and
    whether it starts one."""
    keys = [] if seq is None else [seq[:, i] for i in range(seq.shape[1] - 1, -1, -1)]
    perm = np.lexsort(keys + [lanes[:, i] for i in range(lanes.shape[1] - 1, -1, -1)])
    srt = lanes[perm]
    start = np.concatenate([[True], (srt[1:] != srt[:-1]).any(axis=1)])
    return perm, start, np.cumsum(start) - 1


# ---------------------------------------------------------------------------
# kernel-level parity: dedup select
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(5))
@pytest.mark.parametrize("shape", ["one", "narrow", "two", "four"])
@pytest.mark.parametrize("compress", [False, True])
def test_dedup_parity_xla_numpy(seed, shape, compress):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(3, 2500))
    lanes = _rand_lanes(rng, n, shape)
    oracle = np.asarray(_dedup_oracle(lanes))
    xla = deduplicate_resolve(deduplicate_select_async(lanes, None, compress=compress))
    assert xla.tolist() == oracle.tolist()


@pytest.mark.parametrize("seed", range(3))
def test_dedup_parity_with_seq_lanes(seed):
    rng = np.random.default_rng(100 + seed)
    n = int(rng.integers(10, 1500))
    lanes = _rand_lanes(rng, n, "two")
    seq = rng.permutation(n).astype(np.uint32).reshape(-1, 1)
    oracle = np.asarray(_dedup_oracle(lanes, seq))
    xla = deduplicate_resolve(deduplicate_select_async(lanes, seq, compress=False))
    assert xla.tolist() == oracle.tolist()


def _lanes_for(keys):
    return (keys.astype(np.int32).view(np.uint32) ^ np.uint32(0x80000000)).reshape(-1, 1)


@pytest.mark.parametrize("n", [5, 128, 1000, 4096])
def test_dedup_sizes_match_oracle(rng, n):
    keys = rng.integers(0, max(2, n // 3), n).astype(np.int32)
    lanes = _lanes_for(keys)
    assert deduplicate_select(lanes).tolist() == np.asarray(_dedup_oracle(lanes)).tolist()


def test_dedup_exact_power_of_two_no_padding(rng):
    # m == n: no pad rows; the final segment must still close
    keys = np.sort(rng.integers(0, 100, 2048)).astype(np.int32)
    got = deduplicate_select(_lanes_for(keys))
    assert len(got) == len(np.unique(keys))
    assert got.tolist() == np.asarray(_dedup_oracle(_lanes_for(keys))).tolist()


@pytest.mark.parametrize("seed", range(3))
def test_ovc_dedup_matches_oracle(seed):
    """Offset-value coding rides through the device engine unchanged:
    run-sorted composite keys with compression on (the OVC qualifying
    shape) select as the numpy oracle does."""
    rng = np.random.default_rng(600 + seed)
    runs, per = 4, 400
    parts = []
    for _ in range(runs):
        r = np.stack(
            [
                np.sort(rng.integers(0, 1 << 24, per)).astype(np.uint32),
                rng.integers(0, 1 << 16, per).astype(np.uint32),
                rng.integers(0, 1 << 8, per).astype(np.uint32),
            ],
            axis=1,
        )
        r = r[np.lexsort([r[:, 2], r[:, 1], r[:, 0]])]
        parts.append(r)
    lanes = np.concatenate(parts)
    oracle = np.asarray(_numpy_dedup_select(lanes, None, compress=True))
    xla = deduplicate_resolve(deduplicate_select_async(lanes, None, compress=True))
    assert xla.tolist() == oracle.tolist()


# ---------------------------------------------------------------------------
# sorted_segments' keep-last mask
# ---------------------------------------------------------------------------


@jax.jit
def _segments_of_one_lane(kl, pad):
    return M.sorted_segments(1, 0, kl, [], pad)


@pytest.mark.parametrize("m", [1, 3, 127, 129, 200, 2047, 2049, 5000])
def test_sorted_segments_keep_last_matches_numpy(m):
    """Any row count, padded to its bucket: the keep-last mask over the
    valid rows is numpy's, and no padded row is ever selected."""
    rng = np.random.default_rng(m)
    keys = rng.integers(0, max(2, m // 3), m).astype(np.uint32)
    size = M.pad_size(m)
    kl = np.full((1, size), 0xFFFFFFFF, dtype=np.uint32)
    kl[0, :m] = keys
    pad = np.zeros(size, dtype=np.uint32)
    pad[m:] = 1
    pad_sorted, perm, _, keep_last, _ = (np.asarray(x) for x in _segments_of_one_lane(kl, pad))
    srt = np.sort(keys)
    expect = np.concatenate([srt[1:] != srt[:-1], [True]])
    assert perm[:m].tolist() == np.argsort(keys, kind="stable").tolist()
    assert keep_last[:m].tolist() == expect.tolist()
    selected = keep_last & (pad_sorted == 0)
    assert not selected[m:].any()


def test_sorted_segments_pad_rows_close_but_are_never_kept():
    """The raw keep-last closes the trailing pad segment too; the select
    mask every dedup kernel applies (pad_sorted == 0) drops it."""
    keys = np.array([[1, 1, 2, 0, 0]], dtype=np.uint32)  # 2 valid keys + pads
    pad = np.array([0, 0, 0, 1, 1], dtype=np.uint32)
    pad_sorted, _, _, keep_last, _ = (np.asarray(x) for x in _segments_of_one_lane(keys, pad))
    assert keep_last.astype(int).tolist() == [0, 1, 1, 0, 1]
    assert (keep_last & (pad_sorted == 0)).astype(int).tolist() == [0, 1, 1, 0, 0]


# ---------------------------------------------------------------------------
# merge_plan / partial-update / aggregate against numpy
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(3))
def test_merge_plan_parity(seed):
    rng = np.random.default_rng(300 + seed)
    n = int(rng.integers(3, 2000))
    lanes = _rand_lanes(rng, n, "two")
    seq = np.stack([np.zeros(n, np.uint32), rng.permutation(n).astype(np.uint32)], axis=1)
    plan = merge_plan(lanes, seq, compress=False)
    perm, start, seg_id = _segments(lanes, seq)
    assert plan.n == n and plan.m == M.pad_size(n)
    assert plan.perm[:n].tolist() == perm.tolist()
    assert plan.seg_start[:n].tolist() == start.tolist()
    assert plan.keep_last[:n].tolist() == np.concatenate([start[1:], [True]]).tolist()
    assert plan.seg_id[:n].tolist() == seg_id.tolist()


def _partial_update_oracle(lanes, fv, kinds):
    """Per key, in key order: each field's last non-null add after the
    key's last delete (-1: null), whether any add follows that delete, and
    the key's last row."""
    perm, _, seg_id = _segments(lanes)
    is_add = np.isin(kinds, (int(RowKind.INSERT), int(RowKind.UPDATE_AFTER)))
    is_del = kinds == int(RowKind.DELETE)
    src, exists, last = [], [], []
    for g in range(int(seg_id[-1]) + 1):
        rows = perm[seg_id == g]
        dels = np.flatnonzero(is_del[rows])
        after = rows[dels[-1] + 1:] if len(dels) else rows
        adds = after[is_add[after]]
        src.append([int(c[-1]) if len(c) else -1 for c in (adds[fv[f, adds]] for f in range(fv.shape[0]))])
        exists.append(len(adds) > 0)
        last.append(int(rows[-1]))
    return np.array(src).T.tolist(), exists, last


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("null_rate", [0.0, 0.4])
def test_fused_partial_update_parity(seed, null_rate):
    rng = np.random.default_rng(400 + seed)
    n = int(rng.integers(10, 1200))
    lanes = _rand_lanes(rng, n, "one")
    fv = rng.random((3, n)) >= null_rate
    kinds = rng.choice(
        [int(RowKind.INSERT), int(RowKind.UPDATE_AFTER), int(RowKind.DELETE)],
        size=n,
        p=[0.6, 0.3, 0.1],
    ).astype(np.uint8)
    src, exists, last = M.fused_partial_update(lanes, None, fv, kinds, remove_record_on_delete=True, compress=False)
    want_src, want_exists, want_last = _partial_update_oracle(lanes, fv, kinds)
    assert np.asarray(src).tolist() == want_src
    assert np.asarray(exists).tolist() == want_exists
    assert np.asarray(last).tolist() == want_last


@pytest.mark.parametrize("seed", range(3))
def test_fused_aggregate_parity(seed):
    from paimon_tpu.data.batch import Column
    from paimon_tpu.ops import AggregateSpec
    from paimon_tpu.ops.aggregates import fused_aggregate

    rng = np.random.default_rng(500 + seed)
    n = int(rng.integers(10, 1200))
    lanes = _rand_lanes(rng, n, "narrow")
    vals = rng.integers(-50, 50, n).astype(np.int64)
    valid = rng.random(n) >= 0.2
    cols = [Column(vals, valid), Column(np.abs(vals) + 1)]
    specs = [AggregateSpec("sum"), AggregateSpec("max")]
    kinds = np.full(n, int(RowKind.INSERT), dtype=np.uint8)
    (total, peak), take = fused_aggregate(lanes, None, cols, specs, kinds, compress=False)
    perm, _, seg_id = _segments(lanes)
    groups = [perm[seg_id == g] for g in range(int(seg_id[-1]) + 1)]
    any_valid = [bool(valid[rows].any()) for rows in groups]
    assert total.valid_mask().tolist() == any_valid
    assert [int(v) for v, ok in zip(total.values, any_valid) if ok] == [
        int(vals[rows][valid[rows]].sum()) for rows, ok in zip(groups, any_valid) if ok]
    assert peak.values.tolist() == [int((np.abs(vals[rows]) + 1).max()) for rows in groups]
    assert take.tolist() == [int(rows[-1]) for rows in groups]


# ---------------------------------------------------------------------------
# table level: sort-engine x lane-compression x dict-domain matrix
# ---------------------------------------------------------------------------


def _build_matrix_table(tmp_warehouse, rng):
    from paimon_tpu.catalog import FileSystemCatalog
    from paimon_tpu.types import BIGINT, DOUBLE, STRING, RowType

    cat = FileSystemCatalog(tmp_warehouse, commit_user="pm")
    t = cat.create_table(
        "db.pm",
        RowType.of(("k1", STRING(False)), ("k2", BIGINT(False)), ("v", DOUBLE()), ("tag", STRING())),
        primary_keys=["k1", "k2"],
        options={"bucket": "1", "write-only": "true"},
    )
    for _ in range(3):
        n = 900
        k1 = np.array([f"user-{int(x):05d}" for x in rng.integers(0, 400, n)], dtype=object)
        k2 = rng.integers(0, 5, n).astype(np.int64)
        v = rng.random(n)
        tag = np.array(
            [None if rng.random() < 0.3 else f"t{int(x)}" for x in rng.integers(0, 8, n)],
            dtype=object,
        )
        wb = t.new_batch_write_builder()
        w = wb.new_write()
        w.write({"k1": k1, "k2": k2, "v": v, "tag": tag})
        wb.new_commit().commit(w.prepare_commit())
    return t


def test_table_matrix_sort_engines(tmp_warehouse, rng):
    t = _build_matrix_table(tmp_warehouse, rng)
    results = {}
    for engine in SortEngine:
        for compress in ("true", "false"):
            for dd in ("true", "false"):
                tt = t.copy(
                    {
                        "sort-engine": engine.value,
                        "merge.lane-compression": compress,
                        "merge.dict-domain": dd,
                        "cache.data-file.max-memory-size": "0 b",
                    }
                )
                rb = tt.new_read_builder()
                out = rb.new_read().read_all(rb.new_scan().plan())
                results[(engine.value, compress, dd)] = out.to_pylist()
    ref = results[("numpy", "false", "false")]
    assert len(ref) > 0
    for key, rows in results.items():
        assert rows == ref, f"divergent output for {key}"


def test_table_compaction_parity(tmp_warehouse):
    """A full compaction rewrites the same rows under either engine."""
    outs = {}
    for engine in SortEngine:
        tt = _build_matrix_table(f"{tmp_warehouse}/{engine.value}", np.random.default_rng(7)).copy(
            {"sort-engine": engine.value, "write-only": "false"}
        )
        wb = tt.new_batch_write_builder()
        w = wb.new_write()
        w.compact(full=True)
        wb.new_commit().commit(w.prepare_commit())
        rb = tt.new_read_builder()
        outs[engine] = rb.new_read().read_all(rb.new_scan().plan()).to_pylist()
    assert outs[SortEngine.XLA_SEGMENTED] == outs[SortEngine.NUMPY]
    assert len(outs[SortEngine.NUMPY]) > 0


def test_table_sort_compact_parity(tmp_warehouse):
    """Sort-compact's clustering sort (append-only tables): the device sort
    and the host lexsort rewrite twins in the same order."""
    from paimon_tpu.catalog import FileSystemCatalog
    from paimon_tpu.table.sort_compact import sort_compact
    from paimon_tpu.types import BIGINT, DOUBLE, RowType

    outs = {}
    for engine in SortEngine:
        rng_e = np.random.default_rng(11)
        cat = FileSystemCatalog(f"{tmp_warehouse}/{engine.value}", commit_user="sc")
        t = cat.create_table(
            "db.sc",
            RowType.of(("a", BIGINT()), ("b", BIGINT()), ("v", DOUBLE())),
            options={"bucket": "1", "sort-engine": engine.value},
        )
        for _ in range(2):
            n = 1500
            wb = t.new_batch_write_builder()
            w = wb.new_write()
            w.write({"a": rng_e.integers(0, 1 << 16, n), "b": rng_e.integers(0, 1 << 16, n), "v": rng_e.random(n)})
            wb.new_commit().commit(w.prepare_commit())
        sort_compact(t, ["a", "b"], order="zorder")
        rb = t.new_read_builder()
        outs[engine] = rb.new_read().read_all(rb.new_scan().plan()).to_pylist()
    assert outs[SortEngine.XLA_SEGMENTED] == outs[SortEngine.NUMPY]
    assert len(outs[SortEngine.NUMPY]) == 3000


def test_numpy_sort_engine_end_to_end(tmp_warehouse):
    from paimon_tpu.catalog import FileSystemCatalog
    from paimon_tpu.types import BIGINT, DOUBLE, RowType

    cat = FileSystemCatalog(tmp_warehouse, commit_user="np")
    t = cat.create_table(
        "db.np",
        RowType.of(("k", BIGINT()), ("v", DOUBLE())),
        primary_keys=["k"],
        options={"bucket": "1", "sort-engine": "numpy"},
    )
    wb = t.new_batch_write_builder(); w = wb.new_write()
    w.write({"k": [3, 1], "v": [3.0, 1.0]}); wb.new_commit().commit(w.prepare_commit())
    wb = t.new_batch_write_builder(); w = wb.new_write()
    w.write({"k": [1], "v": [11.0]}); wb.new_commit().commit(w.prepare_commit())
    rb = t.new_read_builder()
    assert rb.new_read().read_all(rb.new_scan().plan()).to_pylist() == [(1, 11.0), (3, 3.0)]


def test_numpy_sort_engine_stays_on_host(tmp_warehouse, monkeypatch):
    """sort-engine=numpy must never dispatch device kernels, even on the
    multi-run read path."""
    from paimon_tpu.catalog import FileSystemCatalog
    from paimon_tpu.types import BIGINT, DOUBLE, RowType

    def boom(*a, **k):
        raise AssertionError("device kernel dispatched under sort-engine=numpy")

    monkeypatch.setattr(M, "_dedup_select_fn", boom)
    cat = FileSystemCatalog(tmp_warehouse, commit_user="nph")
    t = cat.create_table(
        "db.nph",
        RowType.of(("k", BIGINT()), ("v", DOUBLE())),
        primary_keys=["k"],
        options={"bucket": "1", "sort-engine": "numpy"},
    )
    for vals in ([3, 1], [1]):
        wb = t.new_batch_write_builder(); w = wb.new_write()
        w.write({"k": vals, "v": [float(x) for x in vals]})
        wb.new_commit().commit(w.prepare_commit())
    rb = t.new_read_builder()
    out = rb.new_read().read_all(rb.new_scan().plan())
    assert out.to_pylist() == [(1, 1.0), (3, 3.0)]


# ---------------------------------------------------------------------------
# which engine: the rules and the values they refuse
# ---------------------------------------------------------------------------


def _executor(opts):
    from paimon_tpu.core.mergefn import MergeExecutor
    from paimon_tpu.options import CoreOptions, Options
    from paimon_tpu.types import BIGINT, RowType

    return MergeExecutor(RowType.of(("k", BIGINT(False)), ("v", BIGINT())), ["k"], options=CoreOptions(Options(opts)))


@pytest.mark.parametrize("option,env,cpu,want", [
    ("numpy", {}, False, SortEngine.NUMPY),
    ("xla-segmented", {}, True, SortEngine.XLA_SEGMENTED),
    (None, {"PAIMON_TPU_FORCE_DEVICE_ENGINE": "1"}, True, SortEngine.XLA_SEGMENTED),
    (None, {}, True, SortEngine.NUMPY),
    (None, {}, False, SortEngine.XLA_SEGMENTED),
    (None, {"PAIMON_TPU_SORT_ENGINE": "numpy"}, False, SortEngine.XLA_SEGMENTED),
], ids=["option-numpy-on-device", "option-xla-on-cpu", "forced-device-on-cpu", "unset-on-cpu",
        "unset-on-device", "sort-engine-variable-not-read"])
def test_effective_sort_engine_rule(option, env, cpu, want, monkeypatch):
    """The table's explicit option, else PAIMON_TPU_FORCE_DEVICE_ENGINE=1,
    else numpy on a CPU-only platform, else the device; no other variable
    takes part."""
    monkeypatch.delenv("PAIMON_TPU_FORCE_DEVICE_ENGINE", raising=False)
    for key, value in env.items():
        monkeypatch.setenv(key, value)
    monkeypatch.setattr(M, "resolved_platform_is_cpu", lambda: cpu)
    assert _executor({} if option is None else {"sort-engine": option}).effective_sort_engine() == want


@pytest.mark.parametrize("option,want", [(None, "xla"), ("numpy", "numpy"), ("xla-segmented", "xla")])
def test_sql_reduce_engine_for(option, want, monkeypatch):
    """The SQL segment-reduce runs on the host only where the table chose
    numpy; the environment does not take part."""
    from paimon_tpu.sql.select import _engine_for

    monkeypatch.setenv("PAIMON_TPU_SORT_ENGINE", "numpy")
    table = types.SimpleNamespace(store=_executor({} if option is None else {"sort-engine": option}))
    assert _engine_for(table) == want


def _sort_engine_of(opts):
    return _executor(opts).effective_sort_engine()


def _join_engine_of(opts):
    from paimon_tpu.ops.join import resolve_join_engine

    return resolve_join_engine(opts, rows=1 << 20)


@pytest.mark.parametrize("resolve,option,allowed", [
    (_sort_engine_of, "sort-engine", ("'xla-segmented'", "'numpy'")),
    (_join_engine_of, "join.engine", ("'auto'", "'numpy'", "'xla'")),
], ids=["sort-engine", "join.engine"])
def test_removed_engine_is_rejected_naming_the_allowed_values(resolve, option, allowed, monkeypatch):
    monkeypatch.delenv("PAIMON_TPU_JOIN_ENGINE", raising=False)
    with pytest.raises(ValueError, match=f"^{option}: 'pallas' is not one of ") as err:
        resolve({option: "pallas"})
    assert all(value in str(err.value) for value in allowed)
