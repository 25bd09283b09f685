"""Segment reductions read from scans at the segments' ends
(ops/aggregates.py): integer sums and counts as prefix differences, the
validity bit as a prefix count, first/last picks as running min/max of
positions. Each is checked against the scatter it replaces
(`jax.ops.segment_sum` / `segment_max` here, in the test) and against numpy,
on the planned route (`aggregate_merge` over a `merge_plan`), the fused route
(`fused_aggregate`) and the SQL group-by primitive (`segment_reduce`).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paimon_tpu.catalog import FileSystemCatalog
from paimon_tpu.data.batch import Column
from paimon_tpu.ops import aggregates
from paimon_tpu.ops.aggregates import AggregateSpec, aggregate_merge, fused_aggregate, segment_reduce, takes_scan
from paimon_tpu.ops.merge import merge_plan, pad_size
from paimon_tpu.types import BIGINT, INT, RowKind, RowType

INTS = (np.int8, np.int16, np.int32, np.int64)
INSERT, RETRACT = int(RowKind.INSERT), int(RowKind.UPDATE_BEFORE)
# (rows, keys): one segment, one row, single-row segments, and rows just
# under, at and over a pad boundary
LAYOUTS = [(300, 1), (1, 1), (200, 200), (127, 40), (128, 40), (129, 40), (1000, 333)]


def _keys(rng, n, k):
    """n keys over k distinct values, every value used, in shuffled order."""
    keys = np.concatenate([np.arange(k), rng.integers(0, k, n - k)]).astype(np.uint32)
    return rng.permutation(keys)


def _values(rng, n, dtype):
    """Near the dtype's limits, either sign: a few rows' sum wraps."""
    info = np.iinfo(dtype)
    return rng.integers(info.min // 2, info.max // 2, n, dtype=np.int64, endpoint=True).astype(dtype) * 2 + 1


def _expected(keys, kind, column, fn):
    """numpy, key by key in key order, rows in input order: (values, valid)."""
    valid = column.valid_mask()
    outs, oks = [], []
    for key in np.unique(keys):
        rows = np.flatnonzero(keys == key)
        ok = rows[valid[rows]]
        if fn in ("sum", "count"):
            sign = np.where(kind[ok] == RETRACT, -1, 1)
            if fn == "count":
                outs.append(int(sign.sum()))
                oks.append(True)
                continue
            total = int((column.values[ok].astype(object) * sign).sum()) if len(ok) else 0
            # wrapping arithmetic of the column's width
            bits = column.values.dtype.itemsize * 8
            total = (total + 2 ** (bits - 1)) % 2**bits - 2 ** (bits - 1)
            outs.append(total)
            oks.append(len(ok) > 0)
            continue
        pool = rows if fn in ("first_value", "last_value", "primary-key") else ok
        if not len(pool):
            outs.append(0)
            oks.append(False)
            continue
        row = pool[0] if fn.startswith("first") else pool[-1]
        outs.append(column.values[row])
        oks.append(bool(valid[row]))
    return np.array(outs, dtype=column.values.dtype if fn != "count" else np.int64), np.array(oks, dtype=np.bool_)


def _assert_column(got: Column, want):
    values, valid = want
    assert np.array_equal(got.valid_mask(), valid)
    assert np.array_equal(np.asarray(got.values)[valid], values[valid])


def _case(seed, n, k, dtype, retract=True, null_share=0.3):
    rng = np.random.default_rng(seed)
    keys = _keys(rng, n, k)
    valid = rng.random(n) >= null_share
    if n > 3:
        valid[keys == keys[0]] = False  # a segment of nulls alone
    kind = np.where(rng.random(n) < 0.25, RETRACT, INSERT).astype(np.uint8) if retract else np.full(n, INSERT, np.uint8)
    return keys, kind, Column(_values(rng, n, dtype), valid)


# ---- the in-kernel helpers against the scatter ---------------------------------


@pytest.mark.parametrize("dtype", INTS)
@pytest.mark.parametrize("n,k", LAYOUTS)
def test_the_prefix_difference_is_the_scatter_add(dtype, n, k):
    rng = np.random.default_rng(n * 7 + k)
    seg_id = np.sort(_keys(rng, n, k)).astype(np.int32)
    m = pad_size(n)
    contrib = np.zeros(m, dtype)
    contrib[:n] = _values(rng, n, dtype)
    ok = np.zeros(m, np.bool_)
    ok[:n] = rng.random(n) < 0.5
    seg = np.full(m, k, np.int32)  # the pad rows: one segment after the valid ones
    seg[:n] = seg_id
    ends = np.flatnonzero(np.r_[seg_id[1:] != seg_id[:-1], True]).astype(np.int32)
    got, anyv = jax.jit(lambda c, o, e: (aggregates._segment_sum(c, e), aggregates._any_valid(o, e)))(contrib, ok, ends)
    scatter = jax.ops.segment_sum(jnp.asarray(contrib), jnp.asarray(seg), num_segments=m)[:k]
    assert np.array_equal(np.asarray(got), np.asarray(scatter))
    starts = np.r_[0, ends[:-1] + 1]
    assert np.array_equal(np.asarray(got), np.add.reduceat(contrib[:n].astype(np.int64), starts).astype(dtype))
    want_any = jax.ops.segment_max(jnp.asarray(ok.astype(np.int32)), jnp.asarray(seg), num_segments=m)[:k] > 0
    assert np.array_equal(np.asarray(anyv), np.asarray(want_any))


@pytest.mark.parametrize("n,k", LAYOUTS)
@pytest.mark.parametrize("last", [True, False])
def test_a_pick_is_the_segment_max_or_min_of_the_positions(n, k, last):
    rng = np.random.default_rng(n + k)
    seg_id = np.sort(_keys(rng, n, k)).astype(np.int32)
    ok = rng.random(n) < 0.4
    ends = np.flatnonzero(np.r_[seg_id[1:] != seg_id[:-1], True]).astype(np.int32)
    got = np.asarray(jax.jit(lambda o, e: aggregates._pick(o, e, last))(ok, ends))
    pos = np.arange(n, dtype=np.int32)
    if last:
        want = jax.ops.segment_max(jnp.where(ok, pos, -1), seg_id, num_segments=k)
    else:
        want = jax.ops.segment_min(jnp.where(ok, pos, n), seg_id, num_segments=k)
        want = jnp.where(want == n, -1, want)
    assert np.array_equal(got, np.asarray(want))


# ---- the planned route ---------------------------------------------------------


@pytest.mark.parametrize("dtype", INTS)
@pytest.mark.parametrize("n,k", LAYOUTS)
def test_planned_sums_and_counts_wrap_and_retract(dtype, n, k):
    keys, kind, column = _case(n * 31 + k, n, k, dtype)
    plan = merge_plan(keys.reshape(-1, 1))
    for fn in ("sum", "count"):
        got = aggregate_merge(plan, column, AggregateSpec(fn), kind)
        _assert_column(got, _expected(keys, kind, column, fn))
        assert got.values.dtype == (column.values.dtype if fn == "sum" else np.int64)


@pytest.mark.parametrize("fn", ["first_value", "first_non_null_value", "last_value", "last_non_null_value",
                                "primary-key"])
@pytest.mark.parametrize("n,k", LAYOUTS)
def test_planned_picks(fn, n, k):
    keys, kind, column = _case(n * 13 + k, n, k, np.int32, retract=False)
    plan = merge_plan(keys.reshape(-1, 1))
    _assert_column(aggregate_merge(plan, column, AggregateSpec(fn), kind), _expected(keys, kind, column, fn))


def test_planned_picks_of_strings_and_an_ignored_retraction():
    keys, kind, _ = _case(5, 500, 120, np.int32)
    rng = np.random.default_rng(5)
    valid = rng.random(500) >= 0.3
    strings = np.array([f"s{i}" if v else None for i, v in enumerate(valid)], dtype=object)
    column = Column(strings, valid)
    plan = merge_plan(keys.reshape(-1, 1))
    got = aggregate_merge(plan, column, AggregateSpec("last_non_null_value", ignore_retract=True), kind)
    # the retract rows are not candidates
    want = _expected(keys, kind, Column(strings, valid & (kind == INSERT)), "last_non_null_value")
    _assert_column(got, want)


def test_planned_float32_sums_keep_the_scatter():
    rng = np.random.default_rng(3)
    keys = _keys(rng, 900, 250)
    column = Column(rng.standard_normal(900).astype(np.float32) * 1e6)
    kind = np.full(900, INSERT, np.uint8)
    spec = AggregateSpec("sum")
    assert not takes_scan(spec, column) and takes_scan(spec, Column(column.values.astype(np.int32)))
    plan = merge_plan(keys.reshape(-1, 1))
    got = aggregate_merge(plan, column, spec, kind)
    # the scatter over the sorted rows, as the kernel runs it: the same bits
    contrib = column.values[plan.perm[: plan.n]]
    want = jax.ops.segment_sum(contrib, plan.seg_id[: plan.n], num_segments=plan.num_segments)
    assert np.array_equal(got.values, np.asarray(want))
    perm, ends, seg = np.arange(256, dtype=np.int32), np.arange(1, 256, 2, dtype=np.int32), np.arange(256) // 2
    ones, valid = np.ones(256, np.float32), np.ones(256, np.bool_)
    assert "scatter" in aggregates._sum_fn().lower(perm, ends, seg.astype(np.int32), ones, valid, ones).as_text()


# ---- the fused route and segment_reduce --------------------------------------


@pytest.mark.parametrize("dtype", INTS)
@pytest.mark.parametrize("n,k", LAYOUTS)
def test_fused_route_sums_counts_and_picks(dtype, n, k):
    keys, kind, column = _case(n * 17 + k, n, k, dtype)
    _, _, pick_col = _case(n * 19 + k, n, k, np.int64, retract=False)
    names = ("sum", "count", "max", "last_non_null_value", "first_non_null_value", "last_value")
    specs = [AggregateSpec(fn, ignore_retract=fn not in ("sum", "count")) for fn in names]
    cols = [column, column, column] + [pick_col] * 3
    got, last_take = fused_aggregate(keys.reshape(-1, 1), None, cols, specs, kind)
    assert len(last_take) == k and sorted(keys[last_take]) == list(range(k))
    include = kind == INSERT
    for fn, col, out in zip(names, cols, got):
        if fn in ("sum", "count"):
            _assert_column(out, _expected(keys, kind, col, fn))
        elif fn == "max":
            v = np.where(col.valid_mask() & include, col.values, np.iinfo(dtype).min)
            want = np.array([v[keys == key].max() for key in range(k)], dtype)
            ok = np.array([(col.valid_mask() & include)[keys == key].any() for key in range(k)])
            _assert_column(out, (want, ok))
        else:
            sub = Column(col.values, col.valid_mask() & include) if "non_null" in fn else col
            want = _expected(keys[include], kind[include], Column(sub.values[include], sub.valid_mask()[include]), fn)
            present = np.isin(np.arange(k), keys[include])
            vals = np.zeros(k, col.values.dtype)
            vals[present] = want[0]
            oks = np.zeros(k, np.bool_)
            oks[present] = want[1]
            _assert_column(out, (vals, oks))


@pytest.mark.parametrize("dtype", INTS)
@pytest.mark.parametrize("n,k", LAYOUTS)
def test_segment_reduce_sums_and_counts_as_its_numpy_twin(dtype, n, k):
    rng = np.random.default_rng(n * 3 + k)
    keys = _keys(rng, n, k).reshape(-1, 1)
    values = _values(rng, n, dtype)
    valid = rng.random(n) >= 0.3
    cols = [(values, valid), (np.ones(n, np.int64), valid), (values, valid)]
    fns = ("sum", "count", "min")
    rep, outs, anyv, first = segment_reduce(keys, cols, fns)
    want = aggregates.segment_reduce_np(keys, cols, fns, np.arange(n, dtype=np.int64))
    assert np.array_equal(keys[rep], keys[want[0]]) and np.array_equal(first, want[3])
    for i in range(3):
        assert np.array_equal(anyv[i], want[2][i])
        assert np.array_equal(outs[i][anyv[i]], want[1][i].astype(outs[i].dtype)[anyv[i]]), fns[i]


# ---- programs: no scatter, one compile a bucket -------------------------------


@pytest.mark.parametrize("program", ["agg_sum", "agg_pick"])
def test_the_integer_programs_hold_no_scatter(program):
    m = 512
    perm, ends = np.arange(m, dtype=np.int32), np.arange(2, m, 3, dtype=np.int32)
    valid = np.ones(m, np.bool_)
    if program == "agg_sum":
        lowered = aggregates._sum_fn().lower(perm, ends, None, np.ones(m, np.int64), valid, np.ones(m, np.int64))
    else:
        lowered = aggregates._pick_fn(True).lower(perm, ends, valid)
    text = lowered.as_text()
    assert f"module @jit_{program} " in text and "scatter" not in text


def test_merges_of_different_key_counts_in_one_bucket_compile_once():
    rng = np.random.default_rng(11)
    kind = np.full(700, INSERT, np.uint8)
    column = Column(_values(rng, 700, np.int64))
    plans = [merge_plan(_keys(rng, 700, k).reshape(-1, 1)) for k in (300, 450)]
    assert {p.m for p in plans} == {1024} and {len(p.segment_ends) for p in plans} == {512}
    aggregate_merge(plans[0], column, AggregateSpec("sum"), kind)
    aggregate_merge(plans[0], column, AggregateSpec("last_value"), kind)
    compiled = aggregates._sum_fn()._cache_size(), aggregates._pick_fn(True)._cache_size()
    got = aggregate_merge(plans[1], column, AggregateSpec("sum"), kind)
    aggregate_merge(plans[1], column, AggregateSpec("last_value"), kind)
    assert (aggregates._sum_fn()._cache_size(), aggregates._pick_fn(True)._cache_size()) == compiled
    assert len(got) == 450


# ---- partial-update sequence groups -------------------------------------------


def test_a_sum_inside_a_sequence_group_wraps_and_keeps_its_group(tmp_warehouse):
    cat = FileSystemCatalog(tmp_warehouse, commit_user="sgs")
    schema = RowType.of(("k", BIGINT()), ("total", INT()), ("g", BIGINT()), ("last", BIGINT()), ("h", BIGINT()))
    t = cat.create_table(
        "db.sgs", schema, primary_keys=["k"],
        options={"bucket": "1", "merge-engine": "partial-update",
                 "fields.g.sequence-group": "total", "fields.total.aggregate-function": "sum",
                 "fields.h.sequence-group": "last"},
    )

    def write(data):
        wb = t.new_batch_write_builder()
        w = wb.new_write()
        w.write(data)
        wb.new_commit().commit(w.prepare_commit())

    big = 2_000_000_000
    write({"k": [1, 1, 2, 3], "total": [big, big, -5, None], "g": [1, 2, 1, 1], "last": [10, 11, 20, 30],
           "h": [5, 4, 1, None]})
    write({"k": [1, 2, 2], "total": [7, 3, None], "g": [3, None, 2], "last": [12, 21, 22], "h": [3, 2, None]})
    rb = t.new_read_builder()
    out = sorted(rb.new_read().read_all(rb.new_scan().plan()).to_pylist())
    wrapped = (2 * big + 7 + 2**31) % 2**32 - 2**31
    # key 2: the row with a null g is not in the group; key 3: nothing to sum;
    # `last` takes its group's highest h (key 1: h=5, its first row)
    assert out == [(1, wrapped, 3, 10, 5), (2, -5, 2, 21, 2), (3, None, 1, None, None)]
