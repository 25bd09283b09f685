"""Device merge kernel vs brute-force numpy/python oracles.

Mirrors the reference's SortMergeReaderTestBase + merge function tests
(reference paimon-core/src/test/java/org/apache/paimon/mergetree/compact/):
results must be byte-identical to a straightforward per-key interpretation.
"""

import os

import numpy as np
import pytest

from paimon_tpu.data import ColumnBatch
from paimon_tpu.data.keys import encode_key_lanes, split_int64_lanes
from paimon_tpu.ops import (
    AggregateSpec,
    MergePlan,
    aggregate_merge,
    deduplicate_take,
    first_row_take,
    merge_plan,
    partial_update_takes,
)
from paimon_tpu.data.batch import Column
from paimon_tpu.types import BIGINT, INT, RowKind, RowType


def make_inputs(rng, n=500, key_space=120):
    keys = rng.integers(0, key_space, n).astype(np.int64)
    seq = np.arange(n, dtype=np.int64)
    rng.shuffle(seq)  # unique but unordered sequence numbers
    kinds = rng.choice(
        [int(RowKind.INSERT), int(RowKind.UPDATE_AFTER), int(RowKind.DELETE)], size=n, p=[0.6, 0.3, 0.1]
    ).astype(np.uint8)
    vals = rng.integers(-1000, 1000, n).astype(np.int64)
    return keys, seq, kinds, vals


def plan_for(keys, seq):
    schema = RowType.of(("k", BIGINT(False)))
    b = ColumnBatch.from_pydict(schema, {"k": keys.tolist()})
    lanes = encode_key_lanes(b, ["k"])
    hi, lo = split_int64_lanes(seq)
    return merge_plan(lanes, np.stack([hi, lo], axis=1))


def test_plan_orders_and_segments(rng):
    keys, seq, _, _ = make_inputs(rng, 300, 40)
    plan = plan_for(keys, seq)
    assert plan.n == 300
    order = plan.perm[plan.valid_sorted]
    ks = keys.take(order)
    ss = seq.take(order)
    # sorted by (key, seq)
    assert all((ks[i], ss[i]) <= (ks[i + 1], ss[i + 1]) for i in range(len(ks) - 1))
    # segments = distinct keys
    assert plan.num_segments == len(np.unique(keys))
    starts = plan.seg_start[plan.valid_sorted]
    assert starts.sum() == plan.num_segments
    assert (np.flatnonzero(np.diff(ks) != 0) + 1 == np.flatnonzero(starts)[1:]).all()


def test_deduplicate_matches_oracle(rng):
    keys, seq, kinds, vals = make_inputs(rng)
    plan = plan_for(keys, seq)
    take = deduplicate_take(plan)
    # oracle: per key, row with max seq
    oracle = {}
    for i in range(len(keys)):
        k = keys[i]
        if k not in oracle or seq[oracle[k]] < seq[i]:
            oracle[k] = i
    expect = [oracle[k] for k in sorted(oracle)]
    assert take.tolist() == expect


def test_deduplicate_tie_break_input_order():
    # equal (key, seq): later input wins under "last row" semantics
    keys = np.array([5, 5, 5], dtype=np.int64)
    seq = np.array([7, 7, 7], dtype=np.int64)
    plan = plan_for(keys, seq)
    assert deduplicate_take(plan).tolist() == [2]
    assert first_row_take(plan).tolist() == [0]


def test_first_row_matches_oracle(rng):
    keys, seq, _, _ = make_inputs(rng)
    plan = plan_for(keys, seq)
    take = first_row_take(plan)
    oracle = {}
    for i in range(len(keys)):
        k = keys[i]
        if k not in oracle or seq[oracle[k]] > seq[i]:
            oracle[k] = i
    assert take.tolist() == [oracle[k] for k in sorted(oracle)]


def test_partial_update_matches_oracle(rng):
    n = 400
    keys, seq, kinds, _ = make_inputs(rng, n, 60)
    kinds = np.where(kinds == int(RowKind.DELETE), int(RowKind.INSERT), kinds).astype(np.uint8)  # adds only here
    f0 = rng.integers(0, 100, n).astype(np.int64)
    f0_valid = rng.random(n) > 0.4
    f1 = rng.integers(0, 100, n).astype(np.int64)
    f1_valid = rng.random(n) > 0.4
    plan = plan_for(keys, seq)
    src, exists = partial_update_takes(plan, np.stack([f0_valid, f1_valid]), kinds)
    assert exists.all()
    uniq = sorted(set(keys.tolist()))
    assert src.shape == (2, len(uniq))
    for fi, (fv,) in enumerate([(f0_valid,), (f1_valid,)]):
        for si, k in enumerate(uniq):
            rows = [i for i in range(n) if keys[i] == k and fv[i]]
            expect = max(rows, key=lambda i: seq[i]) if rows else -1
            assert src[fi, si] == expect, (fi, k)


def test_partial_update_remove_record_on_delete():
    keys = np.array([1, 1, 1, 2, 2], dtype=np.int64)
    seq = np.array([0, 1, 2, 0, 1], dtype=np.int64)
    kinds = np.array(
        [RowKind.INSERT, RowKind.DELETE, RowKind.INSERT, RowKind.INSERT, RowKind.DELETE], dtype=np.uint8
    )
    valid = np.ones((1, 5), dtype=np.bool_)
    plan = plan_for(keys, seq)
    src, exists = partial_update_takes(plan, valid, kinds, remove_record_on_delete=True)
    # key 1: delete at seq1 wipes seq0; seq2 insert survives. key 2: deleted.
    assert exists.tolist() == [True, False]
    assert src[0, 0] == 2


@pytest.mark.parametrize(
    "fn", ["sum", "count", "max", "min", "first_value", "first_non_null_value", "last_value", "last_non_null_value", "product"]
)
def test_aggregate_matches_oracle(rng, fn):
    n = 300
    keys, seq, _, vals = make_inputs(rng, n, 50)
    kinds = np.full(n, int(RowKind.INSERT), dtype=np.uint8)
    valid = rng.random(n) > 0.3
    plan = plan_for(keys, seq)
    col = Column(vals.copy(), valid.copy())
    out = aggregate_merge(plan, col, AggregateSpec(fn), kinds)
    uniq = sorted(set(keys.tolist()))
    order = {k: sorted([i for i in range(n) if keys[i] == k], key=lambda i: seq[i]) for k in uniq}
    for si, k in enumerate(uniq):
        rows = order[k]
        vs = [vals[i] for i in rows if valid[i]]
        got = out.to_pylist()[si]
        if fn == "sum":
            assert got == (sum(vs) if vs else None)
        elif fn == "count":
            assert got == len(vs)
        elif fn == "max":
            assert got == (max(vs) if vs else None)
        elif fn == "min":
            assert got == (min(vs) if vs else None)
        elif fn == "product":
            p = 1
            for v in vs:
                p *= v
            assert got == (p if vs else None)
        elif fn == "first_value":
            assert got == (vals[rows[0]] if valid[rows[0]] else None)
        elif fn == "last_value":
            assert got == (vals[rows[-1]] if valid[rows[-1]] else None)
        elif fn == "first_non_null_value":
            assert got == (vs[0] if vs else None)
        elif fn == "last_non_null_value":
            assert got == (vs[-1] if vs else None)


def test_aggregate_sum_retract(rng):
    keys = np.array([1, 1, 1, 1], dtype=np.int64)
    seq = np.arange(4, dtype=np.int64)
    kinds = np.array([RowKind.INSERT, RowKind.INSERT, RowKind.UPDATE_BEFORE, RowKind.UPDATE_AFTER], dtype=np.uint8)
    vals = np.array([10, 5, 5, 7], dtype=np.int64)
    plan = plan_for(keys, seq)
    out = aggregate_merge(plan, Column(vals), AggregateSpec("sum"), kinds)
    assert out.to_pylist() == [17]  # 10 + 5 - 5 + 7


def test_aggregate_max_rejects_retract():
    keys = np.array([1, 1], dtype=np.int64)
    seq = np.arange(2, dtype=np.int64)
    kinds = np.array([RowKind.INSERT, RowKind.DELETE], dtype=np.uint8)
    plan = plan_for(keys, seq)
    with pytest.raises(ValueError, match="cannot retract"):
        aggregate_merge(plan, Column(np.array([1, 2], dtype=np.int64)), AggregateSpec("max"), kinds)
    # ignore-retract drops the -D row
    out = aggregate_merge(plan, Column(np.array([1, 2], dtype=np.int64)), AggregateSpec("max", ignore_retract=True), kinds)
    assert out.to_pylist() == [1]


def test_aggregate_bool_and_listagg_collect():
    keys = np.array([1, 1, 2, 2, 3], dtype=np.int64)
    seq = np.arange(5, dtype=np.int64)
    kinds = np.full(5, int(RowKind.INSERT), dtype=np.uint8)
    plan = plan_for(keys, seq)
    b = Column(np.array([True, False, True, True, False]))
    assert aggregate_merge(plan, b, AggregateSpec("bool_and"), kinds).to_pylist() == [False, True, False]
    assert aggregate_merge(plan, b, AggregateSpec("bool_or"), kinds).to_pylist() == [True, True, False]
    s = Column(np.array(["a", "b", "c", None, "e"], dtype=object), np.array([1, 1, 1, 0, 1], dtype=np.bool_))
    assert aggregate_merge(plan, s, AggregateSpec("listagg"), kinds).to_pylist() == ["a,b", "c", "e"]
    got = aggregate_merge(plan, s, AggregateSpec("collect"), kinds).to_pylist()
    assert got == [["a", "b"], ["c"], ["e"]]


def test_empty_and_single_row():
    plan = merge_plan(np.zeros((0, 1), dtype=np.uint32))
    assert plan.num_segments == 0
    assert deduplicate_take(plan).tolist() == []
    keys = np.array([42], dtype=np.int64)
    plan1 = plan_for(keys, np.array([0], dtype=np.int64))
    assert deduplicate_take(plan1).tolist() == [0]


def test_large_merge_consistency(rng):
    """8 'sorted runs' concatenated: dedup result == per-run oracle."""
    runs = []
    for r in range(8):
        ks = np.sort(rng.choice(5000, size=2000, replace=False)).astype(np.int64)
        runs.append(ks)
    keys = np.concatenate(runs)
    seq = np.arange(len(keys), dtype=np.int64)
    plan = plan_for(keys, seq)
    take = deduplicate_take(plan)
    oracle = {}
    for i, k in enumerate(keys.tolist()):
        oracle[k] = i  # seq == input order, so last occurrence wins
    assert take.tolist() == [oracle[k] for k in sorted(oracle)]


def test_tiled_dedup_matches_single(rng):
    """Key-range tiled dispatch == single-shot dedup, for key-sorted runs."""
    from paimon_tpu.ops.merge import deduplicate_select, deduplicate_select_tiled

    runs = []
    for r in range(4):
        ks = np.sort(rng.choice(3000, size=1000, replace=False)).astype(np.int32)
        runs.append(ks)
    keys = np.concatenate(runs)
    lanes = (keys.view(np.uint32) ^ np.uint32(0x80000000)).reshape(-1, 1)
    offsets = [0, 1000, 2000, 3000, 4000]
    tiled = deduplicate_select_tiled(lanes, offsets, tile_rows=512)
    single = deduplicate_select(lanes)
    assert tiled.tolist() == single.tolist()


def test_tiled_dedup_batched_multilane(rng):
    """The uniform-batch tile path (one compile for all tiles) stays
    byte-identical to single dispatch for composite keys, mixed u16/u32
    narrowing, uneven runs, and every tile size."""
    from paimon_tpu.ops.merge import deduplicate_select, deduplicate_select_tiled

    runs, offsets = [], [0]
    for size in (5000, 1700, 3100, 900, 2300):
        k0 = np.sort(rng.choice(20_000, size=size, replace=False)).astype(np.uint32)
        k1 = rng.integers(0, 1 << 24, size=size).astype(np.uint32)  # wide: stays u32
        runs.append(np.stack([k0, k1], axis=1))
        offsets.append(offsets[-1] + size)
    lanes = np.concatenate(runs)
    single = deduplicate_select(lanes)
    for tile_rows in (256, 700, 2048, 6000):
        tiled = deduplicate_select_tiled(lanes, offsets, tile_rows=tile_rows)
        assert tiled.tolist() == single.tolist(), f"tile_rows={tile_rows}"


# ---------------------------------------------------------------------------
# round 2: fused partial-update / aggregation kernels vs the plan-based path
# ---------------------------------------------------------------------------


def _mk_exec(schema, keys, engine, opts=None):
    from paimon_tpu.core.mergefn import MergeExecutor
    from paimon_tpu.options import CoreOptions, MergeEngine, Options

    co = CoreOptions(Options({**(opts or {}), "merge-engine": engine}))
    return MergeExecutor(schema, keys, MergeEngine(co.merge_engine), co)


def _kv_random(rng, n=700, keys=60, with_nulls=True, kinds=None):
    from paimon_tpu.core.kv import KVBatch
    from paimon_tpu.data.batch import ColumnBatch
    from paimon_tpu.types import BIGINT, DOUBLE, STRING, RowType

    schema = RowType.of(("id", BIGINT()), ("a", DOUBLE()), ("b", BIGINT()), ("s", STRING()))
    ids = rng.integers(0, keys, n)
    a = rng.normal(size=n)
    b = rng.integers(-50, 50, n)
    s = np.array([f"v{int(x) % 7}" for x in b], dtype=object)
    data = {"id": ids.tolist(), "a": a.tolist(), "b": b.tolist(), "s": s.tolist()}
    if with_nulls:
        data["a"] = [None if i % 5 == 0 else v for i, v in enumerate(data["a"])]
        data["s"] = [None if i % 4 == 0 else v for i, v in enumerate(data["s"])]
    batch = ColumnBatch.from_pydict(schema, data)
    return schema, KVBatch.from_rows(batch, 0, kinds)


def _rows(kv):
    return [tuple(r) + (int(k),) for r, k in zip(kv.data.to_pylist(), kv.kind)]


def test_fused_partial_update_matches_plan_path(rng):
    schema, kv = _kv_random(rng)
    ex = _mk_exec(schema, ["id"], "partial-update")
    fused = ex.merge(kv, seq_ascending=True)  # routes through the fused kernel
    oracle = _mk_exec(schema, ["id"], "partial-update", {"sort-engine": "numpy"})
    # numpy engine takes the plan path
    want = oracle.merge(kv, seq_ascending=True)
    assert _rows(fused) == _rows(want)
    assert (fused.seq == want.seq).all()


def test_fused_partial_update_remove_record_on_delete(rng):
    schema, kv0 = _kv_random(rng, n=400, keys=40)
    kinds = np.where(rng.random(400) < 0.25, 3, 0).astype(np.uint8)  # -D mix
    schema, kv = _kv_random(rng, n=400, keys=40, kinds=kinds)
    opts = {"partial-update.remove-record-on-delete": "true"}
    fused = _mk_exec(schema, ["id"], "partial-update", opts).merge(kv, seq_ascending=True)
    want = _mk_exec(schema, ["id"], "partial-update", {**opts, "sort-engine": "numpy"}).merge(
        kv, seq_ascending=True
    )
    assert _rows(fused) == _rows(want)


def test_fused_aggregation_matches_plan_path(rng):
    opts = {
        "fields.a.aggregate-function": "sum",
        "fields.b.aggregate-function": "max",
        "fields.s.aggregate-function": "last_non_null_value",
    }
    schema, kv = _kv_random(rng)
    fused = _mk_exec(schema, ["id"], "aggregation", opts).merge(kv, seq_ascending=True)
    want = _mk_exec(schema, ["id"], "aggregation", {**opts, "sort-engine": "numpy"}).merge(
        kv, seq_ascending=True
    )
    f_rows, w_rows = _rows(fused), _rows(want)
    assert len(f_rows) == len(w_rows)
    for fr, wr in zip(f_rows, w_rows):
        assert fr[0] == wr[0] and fr[2] == wr[2] and fr[3] == wr[3]
        if fr[1] is None or wr[1] is None:
            assert fr[1] == wr[1]
        else:
            assert abs(fr[1] - wr[1]) < 1e-9  # float sum association tolerance


def test_fused_aggregation_retracts_and_count(rng):
    from paimon_tpu.core.kv import KVBatch
    from paimon_tpu.data.batch import ColumnBatch
    from paimon_tpu.types import BIGINT, RowType

    schema = RowType.of(("id", BIGINT()), ("c", BIGINT()), ("n", BIGINT()))
    n = 300
    ids = rng.integers(0, 20, n)
    kinds = np.where(rng.random(n) < 0.3, 3, 0).astype(np.uint8)  # -D retracts
    data = ColumnBatch.from_pydict(
        schema,
        {"id": ids.tolist(), "c": [1] * n, "n": [None if i % 3 == 0 else 2 for i in range(n)]},
    )
    kv = KVBatch.from_rows(data, 0, kinds)
    opts = {"fields.c.aggregate-function": "sum", "fields.n.aggregate-function": "count"}
    fused = _mk_exec(schema, ["id"], "aggregation", opts).merge(kv, seq_ascending=True)
    want = _mk_exec(schema, ["id"], "aggregation", {**opts, "sort-engine": "numpy"}).merge(
        kv, seq_ascending=True
    )
    assert _rows(fused) == _rows(want)


def test_aggregation_64bit_exactness(rng):
    """x64 regression: BIGINT sums past 2^31 and DOUBLE sums must be exact
    (x32 jax silently truncated both)."""
    from paimon_tpu.core.kv import KVBatch
    from paimon_tpu.data.batch import ColumnBatch
    from paimon_tpu.types import BIGINT, DOUBLE, RowType

    schema = RowType.of(("id", BIGINT()), ("big", BIGINT()), ("d", DOUBLE()))
    big_vals = [3_000_000_000, 4_000_000_001, 5]
    d_vals = [1.0000000123, 2.0000000456, -3.0000000789]
    data = ColumnBatch.from_pydict(schema, {"id": [1, 1, 1], "big": big_vals, "d": d_vals})
    kv = KVBatch.from_rows(data, 0)
    opts = {"fields.big.aggregate-function": "sum", "fields.d.aggregate-function": "sum"}
    out = _mk_exec(schema, ["id"], "aggregation", opts).merge(kv, seq_ascending=True)
    row = out.data.to_pylist()[0]
    assert row[1] == sum(big_vals)  # exact int64, not int32 wraparound
    assert row[2] == d_vals[0] + d_vals[1] + d_vals[2]  # exact f64 association order


def test_f64_host_route_accumulates_in_sequence_order(rng, monkeypatch):
    """The f64-leaves-the-device route (ops/aggregates._host_reduce) is only
    live where default_backend() == "tpu"; forced here so the CPU suite pins
    it: DOUBLE sum/max/min per key must equal a row-by-row fold in
    (key, sequence) order — values chosen so a + (b + c) != (a + b) + c."""
    from paimon_tpu.core.kv import KVBatch
    from paimon_tpu.data.batch import ColumnBatch
    from paimon_tpu.ops import aggregates
    from paimon_tpu.types import BIGINT, DOUBLE, RowType

    monkeypatch.setattr(aggregates, "_f64_on_device_unsupported", lambda: True)
    schema = RowType.of(("id", BIGINT()), ("big", BIGINT()), ("s", DOUBLE()), ("hi", DOUBLE()), ("lo", DOUBLE()))
    n = 600
    ids = rng.integers(0, 40, n)
    big = rng.integers(1_000_000_000, 4_000_000_000, n)
    vals = rng.normal(size=n) * 10.0 ** rng.integers(-8, 8, n)
    cols = {"id": ids.tolist(), "big": big.tolist()}
    for name in ("s", "hi", "lo"):
        cols[name] = [None if i % 7 == 3 else float(v) for i, v in enumerate(vals)]
    kv = KVBatch.from_rows(ColumnBatch.from_pydict(schema, cols), 0)
    opts = {
        "fields.big.aggregate-function": "sum",
        "fields.s.aggregate-function": "sum",
        "fields.hi.aggregate-function": "max",
        "fields.lo.aggregate-function": "min",
    }
    out = _mk_exec(schema, ["id"], "aggregation", opts).merge(kv, seq_ascending=True)
    want = {}
    for i in range(n):  # the sequential oracle: input order is sequence order
        acc = want.setdefault(int(ids[i]), [0, None, None, None])
        acc[0] += int(big[i])
        if i % 7 != 3:
            v = float(vals[i])
            acc[1] = v if acc[1] is None else acc[1] + v
            acc[2] = v if acc[2] is None else max(acc[2], v)
            acc[3] = v if acc[3] is None else min(acc[3], v)
    assert [tuple(r) for r in out.data.to_pylist()] == [(k, *want[k]) for k in sorted(want)]
    # the oracle really is order-sensitive: a pairwise fold disagrees somewhere
    assert any(
        want[k][1] != float(np.sum([float(vals[i]) for i in range(n) if ids[i] == k and i % 7 != 3][::-1]))
        for k in want
        if want[k][1] is not None
    )


def test_lane_narrowing_preserves_selection(rng):
    """Range-narrowed (u8/u16) lane upload selects EXACTLY the same rows as
    the wide u32 path — a constant shift + downcast preserves order and
    segments; the dtype max stays reserved for the pad sentinel."""
    from paimon_tpu.ops import merge as M

    n = 5000
    base = rng.integers(1_000_000, 1_000_000 + 40_000, size=n, dtype=np.uint32)  # u16 range
    tiny = rng.integers(7, 7 + 200, size=n, dtype=np.uint32)  # u8 range
    key_lanes = np.stack([base, tiny], axis=1)
    seq = rng.permutation(n).astype(np.uint32).reshape(n, 1)

    klp, slp, pad, _, k, s, m = M.prepare_lanes(key_lanes, seq)
    assert [a.dtype for a in klp] == [np.dtype(np.uint16), np.dtype(np.uint16)]
    assert pad.dtype == np.dtype(np.uint8)
    wide_bytes = (k + s) * 4 * m
    narrow_bytes = sum(a.nbytes for a in klp) + sum(a.nbytes for a in slp)
    assert narrow_bytes <= wide_bytes / 2  # the link win is real

    got = np.sort(M.deduplicate_select(key_lanes, seq))
    klp_w, slp_w, pad_w, _, kw, sw, _ = M.prepare_lanes(key_lanes, seq, narrow=False)
    packed, count = M._dedup_select_fn(kw, sw)(klp_w, slp_w, pad_w)
    wide = np.sort(np.asarray(packed[: int(count)]))
    assert got.tolist() == wide.tolist()


def test_lane_narrowing_sentinel_boundary(rng):
    """A lane whose range exactly fills u16 must NOT narrow into the
    sentinel value (strict < check)."""
    from paimon_tpu.ops import merge as M

    col = np.array([0, 65534], dtype=np.uint32)  # ptp just under u16 max
    assert M.narrow_lane(col).dtype == np.dtype(np.uint16)
    col2 = np.array([0, 65535], dtype=np.uint32)  # ptp == u16 max: sentinel collision
    assert M.narrow_lane(col2).dtype == np.dtype(np.uint32)


def test_delta_packed_dedup_matches_wide(rng):
    """Delta-packed upload (u16 deltas + per-run bases, device cumsum
    reconstruction) selects exactly the same rows as the wide path."""
    from paimon_tpu.ops import merge as M

    n = 40_000
    # key range must exceed u16 (smaller ranges take the narrowed wide path)
    keys = rng.integers(0, 1 << 20, size=n, dtype=np.uint32)
    runs = 4
    per = n // runs
    lanes = np.empty((n, 1), dtype=np.uint32)
    offsets = [0]
    for r in range(runs):
        lanes[r * per : (r + 1) * per, 0] = np.sort(keys[r * per : (r + 1) * per])
        offsets.append((r + 1) * per)

    handle = M.deduplicate_select_delta_async(lanes, offsets)
    assert handle is not None  # dense ascending runs qualify
    got = np.sort(M.deduplicate_resolve(handle))
    wide = np.sort(M.deduplicate_select(lanes, None))
    assert got.tolist() == wide.tolist()


def test_delta_packed_fallback_conditions(rng):
    from paimon_tpu.ops import merge as M

    # sparse deltas (> u16): fall back
    lanes = np.array([[0], [1 << 20]], dtype=np.uint32)
    assert M.deduplicate_select_delta_async(lanes, [0, 2]) is None
    # multi-lane keys: fall back
    lanes2 = np.zeros((4, 2), dtype=np.uint32)
    assert M.deduplicate_select_delta_async(lanes2, [0, 4]) is None
    # non-ascending run: fall back
    lanes3 = np.array([[1 << 20], [3]], dtype=np.uint32)
    assert M.deduplicate_select_delta_async(lanes3, [0, 2]) is None
    # u16-coverable range: narrowing already wins, delta declines
    lanes4 = np.array([[0], [100]], dtype=np.uint32)
    assert M.deduplicate_select_delta_async(lanes4, [0, 2]) is None
    # trailing EMPTY run (filtered-out file): no crash, correct selection
    lanes5 = np.arange(0, 5 << 18, 1 << 15, dtype=np.uint32).reshape(-1, 1)
    h = M.deduplicate_select_delta_async(lanes5, [0, len(lanes5), len(lanes5)])
    assert h is not None
    assert sorted(M.deduplicate_resolve(h).tolist()) == list(range(len(lanes5)))
    # tiled dispatch still returns correct rows through the fallback
    got = np.sort(M.deduplicate_select_tiled(lanes3, [0, 2]))
    assert got.tolist() == [0, 1]


def _dedup_oracle(lanes: np.ndarray) -> np.ndarray:
    """Expected dedup output: winner per key = greatest input index (runs
    concatenated in ascending-seq order), results in global key order."""
    n = len(lanes)
    order = np.lexsort((np.arange(n),) + tuple(lanes[:, i] for i in reversed(range(lanes.shape[1]))))
    srt = lanes[order]
    neq = (srt[1:] != srt[:-1]).any(axis=1)
    last = np.concatenate([neq, [True]])
    return order[last]


def _runs_fixture(rng, n, runs, key_hi, k=1):
    per = n // runs
    lanes = np.empty((n, k), dtype=np.uint32)
    offsets = [0]
    for r in range(runs):
        lo, hi = r * per, (r + 1) * per if r < runs - 1 else n
        block = rng.integers(0, key_hi, size=(hi - lo, k), dtype=np.uint32)
        idx = np.lexsort(tuple(block[:, i] for i in reversed(range(k))))
        lanes[lo:hi] = block[idx]
        offsets.append(hi)
    return lanes, offsets


def test_compact_selection_exact_order(rng):
    """The compact (bit-packed mask + run-id interleave) download format
    reconstructs EXACTLY the same indices, in the same key order, as the
    int32-index download — across run counts spanning all rbits tiers,
    lane arities, and non-multiple-of-8 row counts."""
    from paimon_tpu.ops import merge as M

    cases = [
        dict(n=40_000, runs=4, key_hi=1 << 20, k=1),   # delta-qualifying, rbits=2
        dict(n=40_000, runs=4, key_hi=1 << 31, k=1),   # sparse: wide compact, rbits=2
        dict(n=30_000, runs=6, key_hi=1 << 20, k=1),   # rbits=4 tier
        dict(n=33_003, runs=20, key_hi=1 << 18, k=1),  # rbits=8 tier, odd n
        dict(n=20_000, runs=4, key_hi=1 << 9, k=2),    # multi-lane: wide compact
        dict(n=5_000, runs=1, key_hi=1 << 14, k=1),    # single run
    ]
    for case in cases:
        lanes, offsets = _runs_fixture(rng, case["n"], case["runs"], case["key_hi"], case["k"])
        handle = M._dedup_dispatch(lanes, offsets, backend="xla")
        got = M.deduplicate_resolve(handle)
        expect = _dedup_oracle(lanes)
        assert got.tolist() == expect.tolist(), case


def test_compact_selection_edge_shapes(rng):
    from paimon_tpu.ops import merge as M

    # empty middle run (filtered-out file)
    lanes = np.array([[5], [9], [1], [9]], dtype=np.uint32)
    handle = M._dedup_dispatch(lanes, [0, 2, 2, 4], backend="xla")
    assert M.deduplicate_resolve(handle).tolist() == _dedup_oracle(lanes).tolist()
    # all keys equal: one winner, the last input row
    lanes2 = np.full((1000, 1), 7, dtype=np.uint32)
    handle2 = M._dedup_dispatch(lanes2, [0, 500, 1000], backend="xla")
    assert M.deduplicate_resolve(handle2).tolist() == [999]
    # duplicate keys WITHIN one run (pre-merged files can't produce this,
    # but the kernel contract allows it): last index still wins
    lanes3 = np.array([[1], [1], [2], [1]], dtype=np.uint32)
    handle3 = M._dedup_dispatch(lanes3, [0, 3, 4], backend="xla")
    assert M.deduplicate_resolve(handle3).tolist() == _dedup_oracle(lanes3).tolist()


def test_compact_selection_through_table_read(tmp_path, rng):
    """End-to-end: the pipelined merge-read (which now downloads the compact
    encoding) returns byte-identical results to the numpy sort engine."""
    import paimon_tpu as pt
    from paimon_tpu.catalog import FileSystemCatalog

    cat = FileSystemCatalog(str(tmp_path), commit_user="t")
    schema = pt.RowType.of(("id", pt.BIGINT(False)), ("v", pt.BIGINT()))
    t = cat.create_table(
        "db.t", schema, primary_keys=["id"],
        options={"bucket": "1", "write-only": "true"},
    )
    ids = rng.permutation(9001).astype(np.int64)
    for r in range(3):
        chunk = np.sort(ids[r * 3000 : (r + 1) * 3000] if r < 2 else ids[6000:])
        wb = t.new_batch_write_builder()
        w = wb.new_write()
        w.write({"id": chunk, "v": chunk * 10 + r})
        wb.new_commit().commit(w.prepare_commit())
    rb = t.new_read_builder()
    out = rb.new_read().read_all(rb.new_scan().plan())
    assert out.num_rows == 9001
    got_ids = np.asarray(out.column("id").values)
    assert got_ids.tolist() == sorted(ids.tolist())
    # every id carries the value from its LAST write
    last_run = {int(i): r for r in range(3) for i in (ids[r * 3000 : (r + 1) * 3000] if r < 2 else ids[6000:])}
    got_v = np.asarray(out.column("v").values)
    assert all(int(v) == int(i) * 10 + last_run[int(i)] for i, v in zip(got_ids, got_v))


def test_compact_selection_many_runs_fallback(rng):
    """Above 256 runs the u8 run-id encoding can't represent the interleave;
    the dispatcher must fall back to the index download and stay exact."""
    from paimon_tpu.ops import merge as M

    n, runs = 6000, 300
    lanes, offsets = _runs_fixture(rng, n, runs, 1 << 30, 1)
    handle = M._dedup_dispatch(lanes, offsets, backend="xla")
    assert not (isinstance(handle, tuple) and handle[0] == "compact")
    got = M.deduplicate_resolve(handle)
    assert got.tolist() == _dedup_oracle(lanes).tolist()


def test_fused_partial_update_blocks(rng):
    """Inputs of 3 to 300 ascending-key blocks, odd sizes, nearly and fully
    null fields: the fused kernel must match the unfused plan oracle exactly."""
    from paimon_tpu.ops import merge as M

    for n, blocks in ((4000, 3), (6003, 12), (9001, 40), (4000, 300)):
        per = max(1, n // blocks)
        keys = np.empty((n, 1), dtype=np.uint32)
        for b in range((n + per - 1) // per):
            lo, hi = b * per, min((b + 1) * per, n)
            keys[lo:hi, 0] = np.sort(rng.integers(0, n // 2, size=hi - lo, dtype=np.uint32))
        F = 3
        fv = rng.random((F, n)) < [[0.7], [0.05], [0.0]]  # incl. nearly/fully null fields
        kinds = np.zeros(n, dtype=np.uint8)
        src, exists, last = M.fused_partial_update(keys, None, fv, kinds)
        plan = M.merge_plan(keys, None)
        src_o, exists_o = M.partial_update_takes(plan, fv, kinds)
        last_o = plan.perm[plan.keep_last & plan.valid_sorted]
        assert last.tolist() == last_o.tolist(), (n, blocks)
        assert exists.tolist() == np.asarray(exists_o).astype(bool).tolist(), (n, blocks)
        assert src.tolist() == np.asarray(src_o).tolist(), (n, blocks)


def _four_sources(rng, keys: int, blocks: int = 10):
    """A wide table's merge input as four sources write it: 14 fields, each
    block an ascending run of one source, the sources writing their fields
    for 100/75/50/25% of the keys in turn; the row kinds mixed, a tenth of
    them deletes."""
    shares = (1.0, 0.75, 0.5, 0.25)
    fields_of = (range(0, 6), range(6, 10), range(10, 14), range(0, 3))
    runs, valid = [], []
    for b in range(blocks):
        source = b % 4
        ids = np.sort(rng.choice(keys, int(shares[source] * keys), replace=False)).astype(np.uint32)
        fv = np.zeros((14, len(ids)), dtype=np.bool_)
        fv[list(fields_of[source])] = rng.random((len(fields_of[source]), len(ids))) < 0.9
        runs.append(ids)
        valid.append(fv)
    key_lanes = np.concatenate(runs).reshape(-1, 1)
    n = len(key_lanes)
    kinds = rng.choice([int(k) for k in (RowKind.INSERT, RowKind.UPDATE_AFTER, RowKind.UPDATE_BEFORE, RowKind.DELETE)],
                       size=n, p=[0.7, 0.15, 0.05, 0.1]).astype(np.uint8)
    return key_lanes, np.arange(n, dtype=np.uint32).reshape(-1, 1), np.concatenate(valid, axis=1), kinds


@pytest.mark.parametrize("remove_record_on_delete", [False, True])
def test_fused_partial_update_at_the_wide_tables_width(rng, remove_record_on_delete):
    from paimon_tpu.ops import merge as M

    keys, seq, fv, kinds = _four_sources(rng, keys=3_000)
    src, exists, last = M.fused_partial_update(keys, seq, fv, kinds, remove_record_on_delete)
    plan = M.merge_plan(keys, seq)
    src_o, exists_o = M.partial_update_takes(plan, fv, kinds, remove_record_on_delete)
    assert last.tolist() == plan.perm[plan.keep_last & plan.valid_sorted].tolist()
    assert exists.tolist() == np.asarray(exists_o).astype(bool).tolist()
    assert src.tolist() == np.asarray(src_o).tolist()
    assert (src >= 0).any(axis=1).all() and (src < 0).any()  # every field written, and nulls left
    assert (~exists).any() == remove_record_on_delete  # every key is added more than once: only a delete drops one


@pytest.mark.parametrize("fields", [0, 30, 31, 45])
def test_fused_partial_update_field_counts(rng, fields):
    """No field, and the field counts about the 32 flags (F fields, an add
    and a delete flag) that fit one word a row."""
    from paimon_tpu.ops import merge as M

    n = 3_000
    keys = np.sort(rng.integers(0, 800, size=(n, 1), dtype=np.uint32), axis=0)
    fv = rng.random((fields, n)) < np.linspace(0.0, 0.9, fields)[:, None]
    kinds = np.where(rng.random(n) < 0.1, int(RowKind.DELETE), int(RowKind.INSERT)).astype(np.uint8)
    src, exists, last = M.fused_partial_update(keys, None, fv, kinds, remove_record_on_delete=True)
    plan = M.merge_plan(keys, None)
    src_o, exists_o = M.partial_update_takes(plan, fv, kinds, remove_record_on_delete=True)
    assert last.tolist() == plan.perm[plan.keep_last & plan.valid_sorted].tolist()
    assert exists.tolist() == np.asarray(exists_o).astype(bool).tolist()
    assert src.shape == (fields, len(last)) and src.tolist() == np.asarray(src_o).tolist()


def test_fused_partial_update_downloads_its_indices(rng, monkeypatch):
    """One merge adds exactly the index download to merge{d2h_bytes}: each
    field's winning position, the existence flags and each key's last
    position, cut on the device to the step that holds the keys; the sort's
    permutation, cut to the step that holds the rows; and the count."""
    from paimon_tpu.metrics import registry
    from paimon_tpu.ops import merge as M

    assert M._link_encodings_pay_off()  # the suite forces the link encodings: they must not matter here
    monkeypatch.setattr(M, "_FETCH_WHOLE_ELEMS", 1024)  # so that the download is cut on the device
    keys, seq, fv, kinds = _four_sources(rng, keys=1_000, blocks=4)
    n, F = len(keys), fv.shape[0]
    registry.reset()
    src, exists, last = M.fused_partial_update(keys, seq, fv, kinds)
    counted = registry.snapshot()["merge"]
    m = M.pad_size(n)
    step = m // M._FETCH_STEPS
    cut, rows_cut = (-(-c // step) * step for c in (len(last), n))
    assert len(last) == 1_000 and cut < rows_cut < m
    # int32 positions, a byte a flag; the count is an int64 (the package runs JAX in x64)
    assert counted["d2h_bytes"] == F * cut * 4 + cut + cut * 4 + rows_cut * 4 + 8
    assert counted["winners"] == 1_000 and src.shape == (F, 1_000) and exists.shape == last.shape == (1_000,)


def test_the_fused_partial_update_program_holds_no_scatter_and_no_loop():
    """The merge's own sort and the pack of the winners are its only sorts:
    no per-field sort, scatter (an index sort on the chip) or loop, which a
    per-field encoding of the selection would bring back."""
    from paimon_tpu.ops import merge as M

    m = 256
    lowered = M._fused_partial_update_fn(1, 1, 14).lower(
        [np.zeros(m, np.uint32)], [np.zeros(m, np.uint32)], np.zeros(m, np.uint8),
        np.zeros((14, m), np.bool_), np.zeros(m, np.bool_), np.zeros(m, np.bool_))
    text = lowered.as_text()
    assert "stablehlo.scatter" not in text and "stablehlo.while" not in text
    assert text.count("stablehlo.sort") == 2


@pytest.mark.skipif(
    os.environ.get("PAIMON_TEST_PLATFORM", "cpu") != "cpu",
    reason="gate-off asserts the configured-cpu dispatch state",
)
def test_dispatch_gate_off_wide_parity(rng, monkeypatch):
    """With the FORCE_COMPACT override removed, the configured-cpu platform
    makes the dispatcher skip every link encoding (no link bytes to save)
    — and the wide path must return exactly the oracle's rows. This pins
    the production CPU-fallback dispatch, which the suite otherwise never
    exercises (conftest forces the device policy on)."""
    from paimon_tpu.ops import merge as M

    monkeypatch.delenv("PAIMON_TPU_FORCE_COMPACT", raising=False)
    assert not M._link_encodings_pay_off()  # conftest pins jax_platforms=cpu
    lanes, offsets = _runs_fixture(rng, 20_000, 4, 1 << 20, 1)
    handle = M._dedup_dispatch(lanes, offsets, backend="xla")
    assert not (isinstance(handle, tuple) and handle[0] == "compact")
    assert M.deduplicate_resolve(handle).tolist() == _dedup_oracle(lanes).tolist()


def test_delta_upload_pallas_and_many_runs(rng):
    """The delta-packed UPLOAD survives past the compact download's limits
    (ADVICE r3): >256 runs and the pallas backend both route through
    _dedup_select_delta_wide_fn (delta upload + index download) instead of
    dropping the upload optimization entirely."""
    from paimon_tpu.ops import merge as M

    n, runs = 13_000, 325
    per = n // runs
    # dense enough that every within-run gap fits u16 (40 samples over 2^17
    # -> mean gap ~3.3k), but a total range past the u16 narrowing threshold
    base = rng.integers(0, 1 << 17, size=n, dtype=np.uint32)
    lanes = np.empty((n, 1), np.uint32)
    offsets = [0]
    for r in range(runs):
        lo, hi = r * per, (r + 1) * per if r < runs - 1 else n
        lanes[lo:hi, 0] = np.sort(base[lo:hi])
        offsets.append(hi)
    h = M.deduplicate_select_delta_async(lanes, offsets)
    assert h is not None and not (isinstance(h, tuple) and h[0] == "compact")
    assert np.sort(M.deduplicate_resolve(h)).tolist() == np.sort(_dedup_oracle(lanes)).tolist()
    # pallas epilogue (interpret mode on cpu) over a small delta-qualifying set
    lanes2, offsets2 = lanes[:4096], [0, 2048, 4096]
    l2 = np.sort(lanes2[:2048, 0]); l3 = np.sort(lanes2[2048:, 0])
    lanes2 = np.concatenate([l2, l3]).reshape(-1, 1)
    hp = M.deduplicate_select_delta_async(lanes2, offsets2, backend="pallas")
    assert hp is not None
    assert np.sort(M.deduplicate_resolve(hp)).tolist() == np.sort(_dedup_oracle(lanes2)).tolist()
